"""The plain reference of the small-I/O cell: what the files hold and which
blocks a job reads, worked out from --seed alone, in plain numpy.

Nothing here imports the program. A file is one draw of bytes from
`default_rng([seed, 5, job])`; a job's batches are consecutive draws of
block numbers, uniform with replacement, from `default_rng([seed, 11,
job])`. A block's fingerprint is what the benchmark's step computes on the
chip: its bytes read as little-endian uint32 words, their sum and the
maximum of word * GOLDEN, both modulo 2^32.
"""

from __future__ import annotations

import numpy as np

from .reference import GOLDEN


def file_bytes(seed: int, job: int, nbytes: int) -> np.ndarray:
    """The whole file of one job, as uint8."""
    return np.random.default_rng([seed, 5, job]).integers(
        0, 256, nbytes, dtype=np.uint8)


def block_draws(seed: int, job: int, blocks_in_file: int, batch: int):
    """An endless iterator over one job's batches: `batch` block numbers
    each, in submission order."""
    rng = np.random.default_rng([seed, 11, job])
    while True:
        yield rng.integers(0, blocks_in_file, batch, dtype=np.int64)


def blocks_of(data: np.ndarray, blocks, bs: int) -> np.ndarray:
    """(len(blocks), bs) uint8: the bytes of the numbered blocks."""
    return data.reshape(-1, bs)[np.asarray(blocks, dtype=np.int64)]


def fingerprints(data: np.ndarray, bs: int):
    """Per block of the file: (uint32 sum, uint32 golden max) of its
    little-endian words."""
    words = data.view("<u4").reshape(-1, bs // 4)
    return (words.sum(axis=1, dtype=np.uint32),
            (words * np.uint32(GOLDEN)).max(axis=1))
