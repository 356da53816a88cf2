"""The plain reference: what the configuration's guarantees say the store
must hold and hand back, worked out from --seed alone.

Nothing here imports the program. CRC32C is google_crc32c's (the
installation's own C library, not the program's kernels). The RS parity is an independent GF(2^8)
encode (shift-and-add multiply, no table of the program's); the code it
encodes IS the stored format and therefore has to be the same code: the
0x11D field, the Cauchy matrix C[i][j] = 1/(i ^ (m+j)), each column divided
by its row-0 entry so that parity row 0 is the plain XOR of the data.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D
GOLDEN = 2654435761  # Knuth's multiplicative constant, as uint32


def gf_mul(a: int, b: int) -> int:
    """Shift-and-add product in GF(2^8) over x^8+x^4+x^3+x^2+1."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= POLY
        b >>= 1
    return out


def gf_inv(a: int) -> int:
    """a^254, by square-and-multiply (a != 0)."""
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of 0")
    out, base, n = 1, a, 254
    while n:
        if n & 1:
            out = gf_mul(out, base)
        base = gf_mul(base, base)
        n >>= 1
    return out


def parity_matrix(k: int, m: int) -> list:
    """m rows of k GF(2^8) coefficients (row 0 all ones)."""
    cauchy = [[gf_inv(i ^ (m + j)) for j in range(k)] for i in range(m)]
    scale = [gf_inv(c) for c in cauchy[0]]
    return [[gf_mul(c, s) for c, s in zip(row, scale)] for row in cauchy]


def _mul_row(c: int) -> np.ndarray:
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def rs_parity(data: np.ndarray, m: int) -> np.ndarray:
    """(k, S) uint8 data shards -> (m, S) parity shards."""
    k, s = data.shape
    out = np.zeros((m, s), dtype=np.uint8)
    for i, row in enumerate(parity_matrix(k, m)):
        for j, c in enumerate(row):
            out[i] ^= _mul_row(c)[data[j]]
    return out


def shard_size(chunk_size: int, k: int) -> int:
    """Bytes per shard of a chunk striped over k data shards: the stored
    format's rule (ceil, then up to a 512-byte grid; 64 below 512)."""
    s0 = -(-chunk_size // k)
    align = 512 if s0 >= 512 else 64
    return -(-s0 // align) * align


def stripe_shards(chunk: bytes, chunk_size: int, k: int, m: int) -> list:
    """The k+m shards a stored chunk must be held as: data shards trimmed
    to the chunk's logical length, parity shards whole."""
    s = shard_size(chunk_size, k)
    padded = np.zeros(k * s, dtype=np.uint8)
    padded[:len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
    data = padded.reshape(k, s)
    parity = rs_parity(data, m)
    out = [bytes(chunk[j * s:(j + 1) * s]) for j in range(k)]
    return out + [parity[i].tobytes() for i in range(m)]


def fingerprint_np(words: np.ndarray) -> tuple:
    """(sum, position-weighted sum) of an unsigned array, both mod 2^32:
    the host twin of lib.device.fingerprint."""
    flat = np.ascontiguousarray(words).reshape(-1).astype(np.uint32)
    w = (np.arange(flat.size, dtype=np.uint32) * np.uint32(GOLDEN)
         + np.uint32(12345))
    return (int(flat.sum(dtype=np.uint32)),
            int((flat * w).sum(dtype=np.uint32)))


def as_unsigned(arr: np.ndarray) -> np.ndarray:
    """Bit pattern of any array as unsigned words of its item size."""
    arr = np.ascontiguousarray(arr)
    return arr.view({1: np.uint8, 2: np.uint16, 4: np.uint32,
                     8: np.uint64}[arr.dtype.itemsize])


def quantiles_lognormal(n: int, median: float, sigma: float,
                        lo: int, hi: int, step: int) -> list:
    """n fixed sizes: the (i+0.5)/n quantiles of a lognormal, clipped and
    rounded to a multiple of `step`. The same set for every seed; the seed
    only reorders."""
    from statistics import NormalDist

    nd = NormalDist()
    out = []
    for i in range(n):
        x = median * float(np.exp(sigma * nd.inv_cdf((i + 0.5) / n)))
        x = min(max(x, lo), hi)
        out.append(max(step, int(round(x / step)) * step))
    return out


def apportion(weights: list, total: int) -> list:
    """Largest-remainder split of `total` slots by weight, each >= 1."""
    wsum = float(sum(weights))
    raw = [w / wsum * total for w in weights]
    counts = [max(1, int(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: raw[i] - int(raw[i]),
                   reverse=True)
    i = 0
    while sum(counts) < total:
        counts[order[i % len(order)]] += 1
        i += 1
    while sum(counts) > total:
        j = max(range(len(counts)), key=lambda i: counts[i])
        counts[j] -= 1
    return counts


# -- the KVCache's stored format, as docs/kvcache.md fixes it -----------------

def chain_keys(token_ids: list, block_tokens: int) -> list:
    """Content address of every full block: blake2b-128 chained over the
    token ids (little-endian int64), from the root tag."""
    import hashlib
    import struct

    parent = b"tpu3fs-kvblock-v1"
    keys = []
    for lo in range(0, len(token_ids) - block_tokens + 1, block_tokens):
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(struct.pack(f"<{block_tokens}q",
                             *token_ids[lo:lo + block_tokens]))
        parent = h.digest()
        keys.append(parent.hex())
    return keys


def entry_path(root: str, key: str) -> str:
    """Two hex levels of the key's own blake2b-128, then the whole hash."""
    import hashlib

    h = hashlib.blake2b(key.encode(), digest_size=16).hexdigest()
    return f"{root}/{h[:2]}/{h[2:4]}/{h}"


def encode_entry(arr: np.ndarray) -> bytes:
    """An array entry: 8-byte dtype name, ndim, the magic 'KVA1', one
    uint64 a dimension, then the raw bytes."""
    import struct

    arr = np.ascontiguousarray(arr)
    head = struct.pack("<8sII", arr.dtype.str.encode(), arr.ndim, 0x4B564131)
    dims = b"".join(struct.pack("<Q", d) for d in arr.shape)
    return head + dims + arr.tobytes()


# -- CRC32C, and the packed record file as docs/dataload.md fixes it ----------

def crc32c(data) -> int:
    """CRC32C (Castagnoli) of a bytes-like, by a library the program does
    not use."""
    import google_crc32c

    return google_crc32c.value(bytes(data))


def record_file_head(records: np.ndarray) -> bytes:
    """Header and index of the record file that holds these fixed-size
    records (n, words): magic "TPRC", version 1, the count, the index's
    CRC32C, 12 reserved bytes; then {offset u64, length u32, crc u32} a
    record, offsets absolute. The payload follows, records back to back."""
    import struct

    rows = np.ascontiguousarray(records).view(np.uint8).reshape(
        records.shape[0], -1)
    n, length = rows.shape
    index = np.zeros(n, dtype=[("offset", "<u8"), ("length", "<u4"),
                               ("crc", "<u4")])
    index["offset"] = 32 + 16 * n + np.arange(n, dtype=np.uint64) * length
    index["length"] = length
    index["crc"] = [crc32c(memoryview(r)) for r in rows]
    raw = index.tobytes()
    return struct.pack("<4sIQI12x", b"TPRC", 1, n, crc32c(raw)) + raw


def record_file_bytes(head: bytes, records: np.ndarray, lo: int,
                      hi: int) -> bytes:
    """Bytes [lo, hi) of that file."""
    payload = np.ascontiguousarray(records).reshape(-1).view(np.uint8)
    n = len(head)
    return head[lo:hi] + payload[max(lo, n) - n:max(hi, n) - n].tobytes()


# -- a checkpoint step's data files, as docs/ckpt.md fixes them ---------------

def ckpt_shard_file(leaf: int, shard: int = 0) -> str:
    """One file per distinct saved shard, `l<leaf>.s<shard>`: the leaf's
    place in the flattened tree (dict keys sorted), the shard's number (an
    array on one chip is one shard), holding the shard's row-major bytes."""
    return f"l{leaf}.s{shard}"
