"""Where JAX's persistent compilation cache lives, decided in one place.

StripeCodec compiles one program per (k, m, S) x power-of-two batch bucket,
so a cold process that serves through the device codec compiles dozens of
shapes. Every entry point of this repo that compiles for the chip
(chip_smoke.py, perfbench/run.py, __graft_entry__.py) calls
enable_compile_cache() before its first jit; services pinned to the CPU do
not.
"""

from __future__ import annotations

import os
from typing import Optional

# fixed, inside the checkout, resolved from this file: the directory is
# part of the cache key's environment, so one that moves never hits
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", ".jax_cache"))


def compile_cache_dir() -> str:
    """JAX_COMPILATION_CACHE_DIR where it is set, else DEFAULT_DIR."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set JAX has already read it and no
    path is set here. A process pinned to the CPU compiles nothing for the
    chip and gets no cache (None): XLA:CPU entries would only pile up in
    the checkout, and its loader logs a page for each one it reads back."""
    import jax

    if (jax.config.jax_platforms or "") == "cpu":
        return None
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # the Mosaic kernels here compile in well under JAX's default one-second
    # floor, and they are exactly what a second process should find
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
