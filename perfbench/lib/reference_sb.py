"""The plain reference of the storage-bench cell: what each generation of
each chunk holds, from --seed alone. Plain numpy; nothing of the program.

A chunk is little-endian 32-bit words. Generation 0 of chunk c is the word
stream mix(i * GOLDEN ^ key(seed, c)), i = 0, 1, ...; generation g + 1 is
generation g with step(seed, c, g + 1) added to every word, mod 2^32 — the
chip derives each rewrite from its resident copy that way, and here any
generation comes from the stream and a sum of g steps. `mix` is murmur3's
32-bit finalizer; every operation is one the chip's uint32 arithmetic does
alike.

The stored form, the CRC32C and the RS(k, m) encode are lib/reference.py's
(google_crc32c; the independent GF(2^8) encode).
"""

from __future__ import annotations

import numpy as np

from . import reference as ref

M32 = 0xFFFFFFFF
C1, C2 = 0x85EBCA6B, 0xC2B2AE35


def mix(x):
    """murmur3 fmix32 of a uint32 array (or of an int, -> int)."""
    if isinstance(x, (int, np.integer)):
        x = int(x) & M32
        x ^= x >> 16
        x = (x * C1) & M32
        x ^= x >> 13
        x = (x * C2) & M32
        return x ^ (x >> 16)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(C1)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(C2)
    return x ^ (x >> np.uint32(16))


def chunk_key(seed: int, chunk: int) -> int:
    """32-bit key of (seed, chunk); a seed of up to 64 bits counts whole."""
    seed &= (1 << 64) - 1
    k = mix((seed & M32) ^ mix(((seed >> 32) + 0x9E3779B9) & M32))
    return mix(k ^ mix((chunk + 0x7F4A7C15) & M32))


def step(seed: int, chunk: int, gen: int) -> int:
    """What generation `gen` adds to every word of the one before (odd:
    never 0, so every word of every generation differs from the last)."""
    return mix(chunk_key(seed, chunk) ^ mix((gen * 0x2545F491) & M32)) | 1


def offset(seed: int, chunk: int, gen: int) -> int:
    """The sum of the steps of generations 1..gen, mod 2^32."""
    return sum(step(seed, chunk, t) for t in range(1, gen + 1)) & M32


def _first_words(seed: int, chunk: int, nbytes: int) -> np.ndarray:
    """Generation 0 of chunk `chunk` as `nbytes` / 4 uint32 words."""
    i = np.arange(nbytes // 4, dtype=np.uint32)
    return mix(i * np.uint32(ref.GOLDEN) ^ np.uint32(chunk_key(seed, chunk)))


def generation(seed: int, chunk: int, gen: int, nbytes: int) -> np.ndarray:
    """Generation `gen` of chunk `chunk`: `nbytes` (a multiple of 4) uint8."""
    words = _first_words(seed, chunk, nbytes)
    words += np.uint32(offset(seed, chunk, gen))
    return words.astype("<u4").view(np.uint8)


def fingerprints(seed: int, chunk: int, gens, nbytes: int) -> dict:
    """{g: reference.fingerprint_np of generation g's bytes} for every g of
    `gens`: what the chip's fingerprint of a row that landed holding that
    generation must read. Generation 0's words are made once."""
    first = _first_words(seed, chunk, nbytes)
    return {g: ref.fingerprint_np(
                (first + np.uint32(offset(seed, chunk, g)))
                .astype("<u4").view(np.uint8))
            for g in set(gens)}


def stored_shards(chunk: bytes, chunk_size: int, k: int, m: int) -> list:
    """The k + m shards every target must hold for this chunk, with the
    CRC32C each must be stored with: [(bytes, crc), ...]."""
    return [(s, ref.crc32c(s))
            for s in ref.stripe_shards(chunk, chunk_size, k, m)]
