"""The plain reference of the degraded read and of the rebuild: RS(k,m)
decode of any k of the k+m shards, by Gauss-Jordan elimination over
GF(2^8) on the stored code's generator (identity over lib/reference.py's
column-normalised Cauchy rows), shift-and-add multiply, plain numpy and
Python. Nothing here imports the program.

The cell's comparison needs only the encode (a rebuilt shard is right when
it equals the reference's shard); this is what the CPU tests hold the
program's reconstruct, degraded read and rebuild worker to.
"""

from __future__ import annotations

import numpy as np

from .reference import gf_inv, gf_mul, parity_matrix


def generator(k: int, m: int) -> list:
    """k+m rows of k coefficients: shard j = row j times the data."""
    eye = [[int(i == j) for j in range(k)] for i in range(k)]
    return eye + parity_matrix(k, m)


def invert(rows: list) -> list:
    """The inverse of a square GF(2^8) matrix, by Gauss-Jordan with the
    identity carried beside it; raises where the matrix is singular."""
    n = len(rows)
    a = [list(r) + [int(i == j) for j in range(n)]
         for i, r in enumerate(rows)]
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r][col]), None)
        if pivot is None:
            raise ValueError("singular: these shards do not span the data")
        a[col], a[pivot] = a[pivot], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(x, inv) for x in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [x ^ gf_mul(f, y) for x, y in zip(a[r], a[col])]
    return [row[n:] for row in a]


def _apply(matrix: list, shards: np.ndarray) -> np.ndarray:
    """(rows x k) coefficients times (k, S) shards -> (rows, S)."""
    out = np.zeros((len(matrix), shards.shape[1]), dtype=np.uint8)
    for i, row in enumerate(matrix):
        for j, c in enumerate(row):
            if c:
                table = np.array([gf_mul(c, x) for x in range(256)],
                                 dtype=np.uint8)
                out[i] ^= table[shards[j]]
    return out


def decode(present_idx: list, present: np.ndarray, k: int, m: int,
           want: list) -> np.ndarray:
    """(k, S) shards at `present_idx` (any k distinct of 0..k+m-1) ->
    (len(want), S): the shards `want`, data or parity."""
    if len(present_idx) != k or len(set(present_idx)) != k:
        raise ValueError(f"need {k} distinct shards, got {present_idx}")
    gen = generator(k, m)
    data = _apply(invert([gen[j] for j in present_idx]),
                  np.asarray(present, dtype=np.uint8))
    return _apply([gen[j] for j in want], data)
