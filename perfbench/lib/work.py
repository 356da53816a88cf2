"""What a kernel's call needs, from its shapes alone, and the chip's peaks.

The counts are of the algorithm, never of one implementation's tiling: a
rewrite of the kernel reads against the same work.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks_of(device_kind: str) -> dict:
    """Peaks of the device JAX reports; a kind not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"perfbench/peaks.json has no device kind "
                       f"{device_kind!r}")
    return table[device_kind]


def encode_work(b: int, k: int, m: int, s: int) -> dict:
    """RS(k,m) encode fused with CRC32C over b stripes of k data shards of
    s bytes: reads b*k*s bytes, writes all b*(k+m)*s shard bytes (the CRCs
    are 4 bytes a shard). The GF(2) bit-matmul is an (8m x 8k) binary
    matrix applied to every byte column: 2*64*m*k integer operations a
    column."""
    return {"bytes": b * k * s + b * (k + m) * s + 4 * b * (k + m),
            "int8_ops": 2 * 64 * m * k * b * s}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """-> (seconds the chip could not beat, which bound sets it)."""
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = work.get("int8_ops", 0) / peaks["int8_ops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
