"""An answer altered where it is produced: one element of one restored leaf
has one bit flipped."""


def plant(ctx) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpu3fs.ckpt import CheckpointLoader

    inner = CheckpointLoader.restore

    def restore(self, step, like=None, **kw):
        tree = inner(self, step, like=like, **kw)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        x = leaves[len(leaves) // 2]
        u = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        bits = lax.bitcast_convert_type(x, u).reshape(-1)
        bits = bits.at[bits.size // 3].set(bits[bits.size // 3] ^ u(1))
        leaves[len(leaves) // 2] = lax.bitcast_convert_type(
            bits.reshape(x.shape), x.dtype)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    CheckpointLoader.restore = restore
