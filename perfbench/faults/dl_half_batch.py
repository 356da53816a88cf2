"""Half of the batch left out: the second half of every batch's rows is the
first half again, the ids as the loader drew them."""

from . import patch_next


def plant(ctx) -> None:
    import jax.numpy as jnp

    def half(batch, _st):
        n = batch.data.shape[0] // 2
        batch.data = jnp.concatenate([batch.data[:n], batch.data[:n]])
        return batch

    patch_next(half)
