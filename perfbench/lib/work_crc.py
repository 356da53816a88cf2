"""What a CRC32C check of landed rows needs, from its shapes alone: counts
of the algorithm, never of one implementation's tiling (as lib/work.py's
encode)."""

from __future__ import annotations


def verify_work(b: int, n: int) -> dict:
    """CRC32C of b rows of n bytes, each compared with its expected value:
    reads the b*n bytes and the b expected values, writes a flag a row.
    CRC32C is a GF(2)-linear map of a row's 8n message bits to 32 register
    bits (plus a constant): 2*8*32 integer operations a byte."""
    return {"bytes": b * n + 4 * b + b,
            "int8_ops": 2 * 8 * 32 * b * n}
