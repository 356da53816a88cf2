"""The benchmark's own small programs on the chip: a fingerprint that
stands for the consumer of what lands in HBM (an attention kernel reads a
loaded block; a training step reads restored state), and random rows made
on the chip from the seed. One shape each, so one compile each."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .reference import GOLDEN

_UNSIGNED = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def bits_of(x):
    """The bit pattern of an array as unsigned words of its item size."""
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        return x
    return lax.bitcast_convert_type(x, _UNSIGNED[x.dtype.itemsize])


@jax.jit
def fingerprint(x):
    """(sum, position-weighted sum) mod 2^32 of an array's bit pattern:
    the device twin of reference.fingerprint_np."""
    flat = bits_of(x).reshape(-1).astype(jnp.uint32)
    w = (jnp.arange(flat.size, dtype=jnp.uint32) * jnp.uint32(GOLDEN)
         + jnp.uint32(12345))
    return jnp.stack([flat.sum(dtype=jnp.uint32),
                      (flat * w).sum(dtype=jnp.uint32)])


@functools.partial(jax.jit, static_argnames=("shape",))
def random_block(seed, turn, j, shape):
    """One block of rows as raw uint16 bit patterns, made on the chip from
    (seed, turn, block number): one program, whatever the numbers."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), turn), j)
    return jax.random.bits(key, shape, jnp.uint16)
