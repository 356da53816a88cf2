"""An answer altered where it is produced: one token of one record of every
batch is another token."""

from . import patch_next


def plant(ctx) -> None:
    def alter(batch, _st):
        batch.data = batch.data.at[3, 17].add(1)
        return batch

    patch_next(alter)
