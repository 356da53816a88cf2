"""Fused Pallas TPU kernel for GF(2) bit-plane matmuls (RS encode/decode).

The jnp path in rs.py (_bit_matmul) materializes three HBM-sized
intermediates per call: the int8 bit-plane expansion (8x the input bytes),
the int32 MXU accumulator (32x the output bytes), and the mod-2 planes.
Measured on chip that makes RS(12,4) encode HBM-bound at a fraction of the
machine. This kernel fuses unpack -> int8 MXU matmul -> mod-2 -> repack
entirely in VMEM, so HBM sees only the uint8 input once and the uint8 output
once — the bandwidth floor of the operation.

Inside the kernel everything stays rank-2 (Mosaic rejects the tiny rank-3
broadcasts the jnp path uses): bit-planes are laid out plane-major (row
t*k + j holds bit t of symbol j), and the coefficient matrix is permuted on
the host to match (see _to_plane_major). rs.RSCode picks this kernel on TPU
backends and falls back to the einsum formulation elsewhere (and interpret
mode covers the kernel logic in CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# lane-dim block of shard bytes processed per grid step; multiple of 128
DEFAULT_BLOCK_S = 4096


def _to_plane_major(A_bits: np.ndarray) -> np.ndarray:
    """Permute an (8m, 8k) symbol-major bit matrix (row i*8+t, col j*8+u —
    the GF.expand_to_bits layout) to plane-major (row t*m+i, col u*k+j)."""
    A = np.asarray(A_bits)
    eight_m, eight_k = A.shape
    m, k = eight_m // 8, eight_k // 8
    out = np.empty_like(A)
    for i in range(m):
        for t in range(8):
            for j in range(k):
                for u in range(8):
                    out[t * m + i, u * k + j] = A[i * 8 + t, j * 8 + u]
    return out


def _gf2_kernel(a_ref, x_ref, o_ref, *, k: int, m: int):
    """One (k, Sb) uint8 block -> (m, Sb) uint8 via the plane-major matrix."""
    # Mosaic doesn't legalize shifts on 8-bit vectors; widen to int32 first
    x = x_ref[0].astype(jnp.int32)                 # (k, Sb)
    planes = [((x >> t) & 1).astype(jnp.int8) for t in range(8)]
    bits = jnp.concatenate(planes, axis=0)         # (8k, Sb) plane-major
    acc = jnp.dot(a_ref[...], bits, preferred_element_type=jnp.int32)
    out = jnp.zeros_like(acc, shape=(m, acc.shape[-1]))
    for t in range(8):
        out = out | ((acc[t * m:(t + 1) * m] & 1) << t)
    o_ref[0] = out.astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("k", "m", "block_s", "interpret")
)
def _gf2_matmul_3d(A_pm, data, *, k: int, m: int, block_s: int,
                   interpret: bool):
    """(B, k, S) uint8 -> (B, m, S) uint8; S must be a multiple of block_s."""
    B, _, S = data.shape
    grid = (B, S // block_s)
    return pl.pallas_call(
        functools.partial(_gf2_kernel, k=k, m=m),
        out_shape=jax.ShapeDtypeStruct((B, m, S), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * m, 8 * k), lambda b, s: (0, 0)),
            pl.BlockSpec((1, k, block_s), lambda b, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, m, block_s), lambda b, s: (b, 0, s)),
        interpret=interpret,
    )(A_pm, data)


def prepare_matrix(A_bits) -> jnp.ndarray:
    """Host-side: symbol-major (8m, 8k) bit matrix -> device plane-major.
    Callers cache the result across calls, and the first call may come
    from inside a trace (a jitted codec step, a shard_map body): build it
    at compile-time-eval so what gets cached is a concrete array, never
    that trace's tracer."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(_to_plane_major(np.asarray(A_bits)),
                           dtype=jnp.int8)


def gf2_matmul(A_pm: jnp.ndarray, data: jnp.ndarray, *,
               interpret: bool = False,
               block_s: int = DEFAULT_BLOCK_S) -> jnp.ndarray:
    """Apply a prepare_matrix()-laid-out (8m, 8k) GF(2) matrix to
    (..., k, S) uint8 symbols -> (..., m, S). Same math as rs._bit_matmul."""
    eight_m, eight_k = A_pm.shape
    m, k = eight_m // 8, eight_k // 8
    *lead, kk, S = data.shape
    assert kk == k, (data.shape, k)
    B = int(np.prod(lead)) if lead else 1
    x = data.reshape(B, k, S)
    bs = min(block_s, _round_up(S, 128))
    pad = (-S) % bs
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    out = _gf2_matmul_3d(A_pm, x, k=k, m=m, block_s=bs,
                         interpret=interpret)
    if pad:
        out = out[:, :, :S]
    return out.reshape(*lead, m, S)


def _round_up(v: int, q: int) -> int:
    return ((v + q - 1) // q) * q


def backend_supports_pallas() -> bool:
    """True when the default backend is a TPU (the only platform this
    kernel lowers for). Backend-initialisation errors propagate: a busy or
    broken chip is an error, never a quiet "use the einsum"."""
    return jax.default_backend() == "tpu"
