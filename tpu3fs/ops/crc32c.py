"""CRC32C (Castagnoli) — scalar gold, combine algebra, and batched TPU kernel.

The reference computes CRC32C per chunk on CPU via folly (checksum type in
src/fbs/storage/Common.h:66-199, combine() included). Here the per-byte table
loop is re-expressed as GF(2) linear algebra so a *batch* of fixed-size chunks
is checksummed with two MXU matmuls:

  1. split each chunk into N blocks of BLK bytes; a precomputed (8*BLK, 32)
     matrix maps each block's message bits to the block's raw CRC register;
  2. a precomputed stack of 32x32 shift matrices (powers of the zero-byte
     state-transition matrix A) combines the N block registers into the chunk
     register, which is then corrected for init/xorout.

This works because the CRC register update is affine over GF(2) in (state,
message): raw(init, M) = A^|M| @ init  XOR  raw(0, M), and raw(0, .) is
linear. The same algebra yields crc32c_combine (concatenation), which the
storage write path uses to stitch per-chunk checksums like the reference's
ChecksumInfo::combine.

Bit-exactness is pinned by tests against standard vectors (e.g.
crc32c(b"123456789") == 0xE3069283).
"""

from __future__ import annotations

import functools
import os
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

from tpu3fs.ops.bitops import (
    np_bits_to_u32,
    np_mat2_mul,
    np_mat2_pow,
    np_u32_to_bits,
    pack_u32,
    unpack_bits_last,
)

_POLY_REFLECTED = 0x82F63B78  # CRC32C, reflected form
_XOROUT = 0xFFFFFFFF


def _make_table() -> np.ndarray:
    table = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY_REFLECTED if c & 1 else c >> 1
        table[i] = c
    return table


_TABLE = _make_table()


def _raw_update(state: int, data: bytes) -> int:
    """Advance the raw CRC register (no init/xorout) over data."""
    c = state & 0xFFFFFFFF
    for b in data:
        c = (c >> 8) ^ int(_TABLE[(c ^ b) & 0xFF])
    return c


@functools.lru_cache(maxsize=1)
def _native_crc():
    """Slice-by-8 CRC32C from the native chunk engine, if buildable.

    The hot storage paths checksum every chunk (ref uses folly's hardware
    crc32c); the pure-Python table loop is the correctness gold but ~1000x
    slower, so it stays as the fallback and test oracle only."""
    try:
        import ctypes
        import subprocess

        from tpu3fs.storage import native_engine as ne

        lib = ne._load_lib()  # build+dlopen serialized under its _lib_lock
        if not hasattr(lib, "ce_crc32c_seed"):
            # stale .so predating ce_crc32c_seed: rebuild (serialized under
            # the same lock as _load_lib's build) and load a fresh handle —
            # a cached old lib must not silently degrade every chunk
            # checksum to the ~1000x Python loop
            with ne._lib_lock:
                subprocess.run(
                    ["make", "-C", os.path.abspath(ne._NATIVE_DIR)],
                    check=True, capture_output=True,
                )
                lib = ctypes.CDLL(ne._LIB_PATH)
        fn = lib.ce_crc32c_seed
        fn.restype = ctypes.c_uint32
        fn.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32]
        return fn
    except Exception:
        return None


def crc32c(data: Union[bytes, bytearray, memoryview, np.ndarray], crc: int = 0) -> int:
    """Scalar gold CRC32C with standard init/xorout; chainable via crc arg."""
    if isinstance(data, np.ndarray):
        data = data.astype(np.uint8).tobytes()
    data = bytes(data)
    fast = _native_crc()
    if fast is not None:
        return fast(data, len(data), crc & 0xFFFFFFFF)
    return _raw_update(crc ^ _XOROUT, data) ^ _XOROUT


def crc32c_py(data: Union[bytes, bytearray, memoryview], crc: int = 0) -> int:
    """Pure-Python reference implementation (test oracle)."""
    return _raw_update((crc & 0xFFFFFFFF) ^ _XOROUT, bytes(data)) ^ _XOROUT


def crc32c_batch_host(rows: np.ndarray) -> np.ndarray:
    """Host-side (numpy in/out) per-row CRC32C — the CPU-backend serving
    path. One native crossing with a thread-pooled HW CRC when the library
    is loadable; the scalar loop otherwise. Host-side kernel selection for
    batched CRC lives HERE (mirrors RSCode.encode_host)."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    from tpu3fs.ops import native_ec

    if native_ec.available():
        return native_ec.crc32c_batch(rows)
    return np.fromiter((crc32c(row.tobytes()) for row in rows),
                       dtype=np.uint32, count=rows.shape[0])


@functools.lru_cache(maxsize=1)
def _byte_shift_matrix() -> np.ndarray:
    """A: 32x32 GF(2) matrix advancing the register through one zero byte."""
    A = np.zeros((32, 32), dtype=np.uint8)
    for i in range(32):
        A[:, i] = np_u32_to_bits(_raw_update(1 << i, b"\x00"))
    return A


@functools.lru_cache(maxsize=64)
def _shift_matrix_pow(nbytes: int) -> np.ndarray:
    return np_mat2_pow(_byte_shift_matrix(), nbytes)


def crc32c_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """CRC of concat(A, B) given crc32c(A), crc32c(B) and len(B) in bytes.

    Derivation: with F = 0xFFFFFFFF and S = A^len_b,
    crc(A||B) = S @ crc(A) XOR crc(B)  (the F terms cancel by linearity).
    """
    if len_b == 0:
        return crc_a
    S = _shift_matrix_pow(int(len_b))
    shifted = np_bits_to_u32((S @ np_u32_to_bits(crc_a).astype(np.int64) & 1))
    return shifted ^ crc_b


@functools.lru_cache(maxsize=64)
def crc32c_zeros(length: int) -> int:
    """CRC32C of ``length`` zero bytes, cached per length.

    The XOR-composition identity (crc32c_xor) needs it once per distinct
    shard size per process; the direct computation through the native
    kernel is a one-time sub-millisecond cost, so no matrix shortcut."""
    if length == 0:
        return 0
    return crc32c(b"\x00" * length)


def crc32c_xor(crc_a: int, crc_b: int, length: int) -> int:
    """CRC of A ^ B for equal-``length`` buffers given their CRCs.

    CRC32C with init/xorout 0xFFFFFFFF is AFFINE over GF(2):
    crc(X) = L(X) ^ f(length) with L linear in the message bits, so
    crc(A^B) = crc(A) ^ crc(B) ^ crc(zeros(length)) — the f terms of A
    and B cancel and one survives via the zero buffer. This is the
    per-hop partial-CRC composition of the pipelined chain encode: a hop
    CRCs only its coefficient-scaled contribution and composes, and the
    final composed value equals the CRC of the fully-accumulated parity
    row iff every hop's XORed bytes matched its CRC'd bytes — the
    engine's validated install then proves the whole relay end to end."""
    return crc_a ^ crc_b ^ crc32c_zeros(length)


@functools.lru_cache(maxsize=16)
def _block_matrix(blk: int) -> np.ndarray:
    """B^T, shape (8*blk, 32): message bits of a blk-byte block -> raw register.

    Column construction uses raw(0, e || 0^d) = A^d @ raw(0, e): start from the
    8 unit responses of the final byte and left-multiply by A per position.
    """
    A = _byte_shift_matrix()
    base = np.zeros((32, 8), dtype=np.uint8)  # columns: bits of last byte
    for t in range(8):
        base[:, t] = np_u32_to_bits(_raw_update(0, bytes([1 << t])))
    B = np.zeros((32, 8 * blk), dtype=np.uint8)
    cur = base
    for p in range(blk - 1, -1, -1):
        B[:, 8 * p : 8 * p + 8] = cur
        if p:
            cur = np_mat2_mul(A, cur)
    return np.ascontiguousarray(B.T)


class BatchCrc32c:
    """Batched CRC32C over fixed-size chunks, MXU-lowered.

    __call__(chunks: (batch, size) uint8) -> (batch,) uint32, bit-exact with
    crc32c(). `size` must be a multiple of `block` (default 512B).
    """

    def __init__(self, size: int, block: int = 512):
        if size % block != 0:
            raise ValueError(f"size {size} not a multiple of block {block}")
        self.size = size
        self.block = block
        self.nblocks = size // block
        B_T = _block_matrix(block).astype(np.int8)  # (8*blk, 32)
        A_blk = np_mat2_pow(_byte_shift_matrix(), block)
        # K[j] = A_blk^(nblocks-1-j): shifts block j's register to the end.
        Ks = np.zeros((self.nblocks, 32, 32), dtype=np.int8)
        cur = np.eye(32, dtype=np.uint8)
        for j in range(self.nblocks - 1, -1, -1):
            Ks[j] = cur
            cur = np_mat2_mul(A_blk, cur)
        # init correction: raw register of `size` zero bytes with init F
        z = np_bits_to_u32(
            np_mat2_pow(_byte_shift_matrix(), size) @ np_u32_to_bits(_XOROUT).astype(np.int64) & 1
        )
        # host numpy: constructing BatchCrc32c must not initialize the
        # jax backend (jit accepts numpy operands; device materialization
        # is lazy, on the first device call)
        self._b_t = B_T
        self._ks = Ks
        self._const = np.uint32(z ^ _XOROUT)
        self._jit = jax.jit(self._compute)

    def compute(self, chunks: jnp.ndarray) -> jnp.ndarray:
        """Traceable (un-jitted) form, for composition inside larger kernels."""
        return self._compute(chunks)

    def _compute(self, chunks: jnp.ndarray) -> jnp.ndarray:
        batch = chunks.shape[0]
        blocks = chunks.reshape(batch, self.nblocks, self.block)
        bits = unpack_bits_last(blocks)  # (batch, N, 8*blk) int8
        regs = (
            jnp.einsum("bnj,jo->bno", bits, self._b_t, preferred_element_type=jnp.int32)
            & 1
        )  # (batch, N, 32)
        out_bits = (
            jnp.einsum(
                "jot,bjt->bo", self._ks, regs.astype(jnp.int8),
                preferred_element_type=jnp.int32,
            )
            & 1
        )  # (batch, 32)
        return pack_u32(out_bits) ^ jnp.uint32(self._const)

    def __call__(self, chunks: jnp.ndarray) -> jnp.ndarray:
        assert chunks.ndim == 2 and chunks.shape[1] == self.size, chunks.shape
        from tpu3fs.ops import pallas_rs

        if (not pallas_rs.backend_supports_pallas()
                and not isinstance(chunks, jax.core.Tracer)):
            # non-TPU backend with concrete data: the HW-CRC batch in
            # native/chunk_engine.cpp is ~100x the jax-CPU matmul lowering
            from tpu3fs.ops import native_ec

            if native_ec.available():
                return native_ec.crc32c_batch(np.asarray(chunks))
        return self._jit(chunks)


class CrcVerifier:
    """Check rows that landed in HBM against the CRC32C they were read
    with (``StorageClient.batch_read(..., with_checksum=True)``): ONE
    device program a batch shape, ``crc_verify_device`` — BatchCrc32c of
    every row, compared with its expected value on the chip, so only a
    flag a row comes back. ``land`` is the whole step a TPU client takes:
    host rows to the device, then the check, traced as the root op
    ``crc.verify`` with stages ``land`` (device_put) and ``check`` (the
    program and the fetch of its flags)."""

    def __init__(self, size: int, block: int = 512):
        self.size = size
        self._crc = BatchCrc32c(size, block=block)
        self._check = jax.jit(self.crc_verify_device)

    def crc_verify_device(self, rows, expected):
        """(B, size) uint8, (B,) uint32 -> (B,) bool: row b's CRC32C is
        expected[b]."""
        return self._crc.compute(rows) == expected

    def check(self, rows, expected) -> np.ndarray:
        """Rows already on the device -> a host bool a row."""
        assert rows.ndim == 2 and rows.shape[1] == self.size, rows.shape
        return np.asarray(self._check(
            rows, np.asarray(expected, dtype=np.uint32)))

    def land(self, rows: np.ndarray, expected, device):
        """(B, size) host uint8 rows -> (the rows on ``device``, a host
        bool a row: it landed with the CRC32C it was read with)."""
        from tpu3fs.analytics import spans as _spans

        with _spans.root_span("crc.verify", nbytes=rows.size):
            with _spans.span("crc.verify", "land", nbytes=rows.size):
                landed = jax.device_put(rows, device)
            with _spans.span("crc.verify", "check", nbytes=rows.shape[0]):
                ok = self.check(landed, expected)
        return landed, ok
