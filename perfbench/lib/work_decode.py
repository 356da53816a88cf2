"""What an RS decode needs, from its shapes alone: counts of the algorithm,
never of one implementation's tiling (as lib/work.py's encode)."""

from __future__ import annotations


def decode_work(b: int, k: int, lost: int, s: int) -> dict:
    """Rebuilding `lost` shards of b stripes from k survivors of s bytes:
    reads b*k*s bytes, writes b*lost*s. The GF(2) bit-matmul is an
    (8 lost x 8k) binary matrix applied to every byte column: 2*64*lost*k
    integer operations a column."""
    return {"bytes": b * k * s + b * lost * s,
            "int8_ops": 2 * 64 * lost * k * b * s}
