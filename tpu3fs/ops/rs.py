"""Reed-Solomon RS(k, m) erasure coding as batched TPU bit-plane matmuls.

Design: a systematic Cauchy generator [I_k ; C] over GF(2^8). Encode/decode
are GF(2^8) matrix products, which we lower to the MXU by expanding the small
coefficient matrix into its (8m x 8k) GF(2) bit matrix and multiplying
bit-planes of the data as int8 (accumulate int32, reduce mod 2) — the
"bit-sliced XOR formulation" TPUs want, since they have no carry-less multiply.

The reference replicates via CRAQ instead of RS (docs/design_notes.md "Data
replication"); RS(k,m) is the added capability from BASELINE.json, and "EC"
exists in the reference only as a chain-table type in the placement solver
(deploy/data_placement/src/model/data_placement.py:30). The encode path plugs
into storage targets behind the same engine switch the reference uses for its
chunk engines (src/storage/store/StorageTarget.h:162).

Layouts: data shards are (..., k, S) uint8; parity (..., m, S); a "shard set"
is the concatenation (..., k+m, S). S is the shard size in bytes.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu3fs.ops.bitops import pack_bits, unpack_bits
from tpu3fs.ops.gf256 import GF


def _bit_matmul(A_bits: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Apply an (8m, 8k) GF(2) matrix to uint8 data (..., k, S) -> (..., m, S)."""
    bits = unpack_bits(data)  # (..., 8k, S) int8
    acc = jnp.einsum(
        "ij,...js->...is", A_bits, bits, preferred_element_type=jnp.int32
    )
    return pack_bits(acc & 1)


def _xor_reduce_shards(shards: jnp.ndarray) -> jnp.ndarray:
    """(..., k, S) uint8 -> (..., 1, S): XOR of the shard rows."""
    out = shards[..., 0, :]
    for j in range(1, shards.shape[-2]):
        out = out ^ shards[..., j, :]
    return out[..., None, :]


class RSCode:
    """RS(k, m): k data shards, m parity shards, tolerates any m erasures."""

    def __init__(self, k: int, m: int):
        if k < 1 or m < 0 or k + m > 256:
            raise ValueError(f"bad RS parameters k={k} m={m}")
        self.k = k
        self.m = m
        cauchy = GF.cauchy_parity_matrix(m, k)  # (m, k) GF(2^8)
        # Column-normalize so parity row 0 is all-ones: C'_ij = C_ij / C_0j.
        # [I ; C D] stays MDS for any invertible diagonal D (every k x k
        # submatrix determinant only picks up unit factors), and an all-ones
        # first parity row makes it a plain XOR of the data shards — so the
        # dominant rebuild case (one lost shard, RAID-style) runs at VPU/HBM
        # byte-XOR speed instead of through the GF(2) bit matmul. Verified
        # exhaustively by the MDS test over erasure patterns.
        if m >= 1:
            scale = np.array([GF.inv(int(c)) for c in cauchy[0]],
                             dtype=np.uint8)
            cauchy = np.stack(
                [GF.mul(row, scale) for row in cauchy], axis=0
            ).astype(np.uint8)
            assert (cauchy[0] == 1).all()
        self.parity_matrix = cauchy
        self.generator = np.concatenate(
            [np.eye(k, dtype=np.uint8), self.parity_matrix], axis=0
        )  # (k+m, k)
        # HOST numpy, not a device array: constructing RSCode must never
        # initialize the jax backend — EC-serving processes (storage
        # servers, FUSE daemons) run the host SIMD path and may have no
        # reachable accelerator at all. jax.jit/einsum accept numpy
        # operands, so device materialization happens lazily on the first
        # actual device-kernel call.
        self._parity_bits = GF.expand_to_bits(self.parity_matrix).astype(
            np.int8)
        # per-instance caches keyed on (present, lost) — instance-held so
        # the device matrices/compiled fns die with the RSCode object
        self._reconstruct_mats: dict = {}
        self._reconstruct_fns: dict = {}
        self._pallas_matrices: dict = {}
        self._decode_operands: dict = {}
        self._einsum_fns: dict = {}
        self._xor_schedule: Optional[list] = None
        self._delta_cols: dict = {}

    # -- kernel selection ---------------------------------------------------
    def _apply_bit_matrix(self, A_bits: jnp.ndarray, key,
                          data: jnp.ndarray,
                          A_sym: np.ndarray = None) -> jnp.ndarray:
        """Apply a symbol-major (8o, 8k) bit matrix via the fastest backend:
        the fused Pallas kernel on TPU; on non-TPU backends the native SIMD
        nibble-table path (when given the symbol matrix and concrete data);
        the jitted einsum form as the last resort and under tracing."""
        from tpu3fs.ops import pallas_rs

        if pallas_rs.backend_supports_pallas():
            A_pm = self._pallas_matrices.get(key)
            if A_pm is None:
                A_pm = pallas_rs.prepare_matrix(np.asarray(A_bits))
                self._pallas_matrices[key] = A_pm
            return pallas_rs.gf2_matmul(A_pm, data)
        if A_sym is not None and not isinstance(data, jax.core.Tracer):
            from tpu3fs.ops import native_ec

            if native_ec.available():
                # plain numpy out: wrapping in a device array here
                # would touch the backend for a pure host computation
                return native_ec.gf_apply(
                    np.asarray(A_sym), np.asarray(data))
        fn = self._einsum_fns.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(_bit_matmul, A_bits))
            self._einsum_fns[key] = fn
        return fn(data)

    # -- encode ------------------------------------------------------------
    def _encode(self, data: jnp.ndarray) -> jnp.ndarray:
        return _bit_matmul(self._parity_bits, data)

    def encode(self, data: jnp.ndarray) -> jnp.ndarray:
        """(..., k, S) uint8 data -> (..., m, S) parity."""
        assert data.shape[-2] == self.k, (data.shape, self.k)
        return self._apply_bit_matrix(self._parity_bits, "encode", data,
                                      A_sym=self.parity_matrix)

    def encode_host(self, data: np.ndarray) -> np.ndarray:
        """Host-side (numpy in, numpy out) encode — the CPU-backend serving
        path. Picks the native SIMD kernel when the library is loadable,
        the numpy LUT gold otherwise. All host-side kernel selection lives
        HERE (stripe.py and callers stay dispatch-free)."""
        from tpu3fs.ops import native_ec

        if native_ec.available():
            return native_ec.gf_apply(self.parity_matrix, data)
        return self.encode_np(data)

    def reconstruct_host(
        self,
        present_idx: Sequence[int],
        lost_idx: Sequence[int],
        present_shards: np.ndarray,
    ) -> np.ndarray:
        """Host-side reconstruction (native SIMD when available)."""
        from tpu3fs.ops import native_ec

        if native_ec.available():
            R = self._reconstruct_matrix(
                tuple(int(i) for i in present_idx),
                tuple(int(i) for i in lost_idx))
            return native_ec.gf_apply(R, np.asarray(present_shards))
        return self.reconstruct_np(present_idx, lost_idx, present_shards)

    def _encode_schedule(self) -> list:
        """XOR-scheduled LUT program for the host encode, cached per code:
        per parity row i, the columns grouped by coefficient value, so

            P_i = XOR_c  MUL[c][ XOR_{j : C_ij == c} D_j ]

        A naive encode pays one 256-entry LUT gather per (i, j) term —
        k*m gathers. Grouping equal coefficients first XOR-accumulates
        their shards at memory speed and gathers ONCE per distinct
        coefficient per row (the XOR-level program optimization of
        PAPERS.md arxiv 1603.05806 applied at LUT-pass granularity);
        row 0 is all-ones by construction, so it costs zero gathers."""
        if self._xor_schedule is None:
            sched = []
            for i in range(self.m):
                by_c: dict = {}
                for j in range(self.k):
                    c = int(self.parity_matrix[i, j])
                    if c:
                        by_c.setdefault(c, []).append(j)
                sched.append(sorted(by_c.items()))
            self._xor_schedule = sched
        return self._xor_schedule

    def encode_np(self, data: np.ndarray) -> np.ndarray:
        """Numpy host encode, XOR-scheduled (see _encode_schedule): shards
        sharing a coefficient XOR-reduce first (memory speed), then one
        256-entry LUT gather per DISTINCT coefficient per row; c==1 groups
        (all of parity row 0 by construction) skip the gather entirely —
        the CPU-backend serving path's gold kernel."""
        data = np.asarray(data, dtype=np.uint8)
        *lead, k, s = data.shape
        assert k == self.k
        flat = data.reshape(-1, k, s)
        out = np.zeros((flat.shape[0], self.m, s), dtype=np.uint8)
        for i, groups in enumerate(self._encode_schedule()):
            for c, cols in groups:
                acc = flat[:, cols[0], :]
                for j in cols[1:]:
                    acc = acc ^ flat[:, j, :]
                if c == 1:
                    out[:, i, :] ^= acc
                else:
                    out[:, i, :] ^= GF.MUL_TABLE[c][acc]
        return out.reshape(*lead, self.m, s)

    # -- delta parity (sub-stripe RMW) --------------------------------------
    def parity_delta_matrix(self, j: int) -> np.ndarray:
        """(m, 1) parity-coefficient column for data shard j, cached —
        the k x m coefficient products of the delta-parity update
        ``P'_i = P_i ^ c_ij * (D'_j ^ D_j)`` (RapidRAID-style in-place
        parity maintenance: a sub-stripe write never re-encodes the
        stripe, it applies the delta through this column)."""
        col = self._delta_cols.get(j)
        if col is None:
            if not 0 <= j < self.k:
                raise ValueError(f"data shard index {j} out of range")
            col = np.ascontiguousarray(
                self.parity_matrix[:, j : j + 1], dtype=np.uint8)
            self._delta_cols[j] = col
        return col

    def delta_parity_host(self, j: int, delta: np.ndarray) -> np.ndarray:
        """Host-side parity delta for a change on data shard j:
        (..., S) uint8 delta (D' ^ D, zero-padded to the shard size)
        -> (..., m, S) rows to XOR into the current parity shards.
        Native SIMD when available, LUT gold otherwise."""
        from tpu3fs.ops import native_ec

        col = self.parity_delta_matrix(j)
        d = np.asarray(delta, dtype=np.uint8)
        lead, s = d.shape[:-1], d.shape[-1]
        if native_ec.available():
            return native_ec.gf_apply(col, d.reshape(*lead, 1, s))
        out = np.empty((*lead, self.m, s), dtype=np.uint8)
        for i in range(self.m):
            c = int(col[i, 0])
            if c == 0:
                out[..., i, :] = 0
            elif c == 1:
                out[..., i, :] = d
            else:
                out[..., i, :] = GF.MUL_TABLE[c][d]
        return out

    def gf_accumulate(self, j: int, data: np.ndarray,
                      acc: np.ndarray) -> np.ndarray:
        """The pipelined-chain-encode hop primitive: XOR data shard j's
        coefficient-scaled contribution into the in-flight parity
        accumulator IN PLACE and return the contribution rows.

        ``data`` is (..., S) uint8 (the hop's raw shard bytes, zero-padded
        to the shard size); ``acc`` is (..., m, S) uint8 and is updated to
        ``acc ^ C[:, j] * data``. Accumulating over j = 0..k-1 yields
        exactly ``encode`` (RapidRAID-style in-chain encoding: parity
        builds hop by hop as the data streams down the chain, arxiv
        1207.6744; the per-hop kernel is the cached coefficient column
        applied through the XOR-program-optimized LUT/native path of
        delta_parity_host, arxiv 2108.02692). The returned (..., m, S)
        contribution is what the hop CRCs for the partial-CRC composition
        (ops.crc32c.crc32c_xor) — returning it costs nothing: it had to
        be materialized to XOR anyway."""
        contrib = self.delta_parity_host(j, data)
        np.bitwise_xor(acc, contrib, out=acc)
        return contrib

    # -- decode ------------------------------------------------------------
    def _reconstruct_matrix(
        self, present: Tuple[int, ...], lost: Tuple[int, ...]
    ) -> np.ndarray:
        """GF matrix R (len(lost), k) with lost = R @ shards[present]."""
        key = (present, lost)
        cached = self._reconstruct_mats.get(key)
        if cached is not None:
            return cached
        assert len(present) == self.k
        sub = self.generator[list(present), :]  # (k, k)
        inv = GF.mat_inv(sub)  # data = inv @ present
        rows = []
        for idx in lost:
            # row of the generator for the lost shard, composed with inv
            rows.append(GF.matmul(self.generator[idx : idx + 1, :], inv)[0])
        R = np.stack(rows, axis=0)
        self._reconstruct_mats[key] = R
        return R

    def reconstruct_fn(
        self, present_idx: Sequence[int], lost_idx: Sequence[int]
    ):
        """Jitted fn mapping (..., k, S) surviving shards -> (..., lost, S).

        The single decode entry point: reconstruct() and the distributed
        rebuild path (tpu3fs.parallel.rebuild) both go through here, so a
        kernel swap (e.g. Pallas) lands in one place.
        """
        present = tuple(int(i) for i in present_idx)
        lost = tuple(int(i) for i in lost_idx)
        key = (present, lost)
        fn = self._reconstruct_fns.get(key)
        if fn is None:
            if self._xor_rebuild_applies(present, lost):
                # single loss covered by the all-ones parity row: the lost
                # shard is the plain XOR of the k survivors — byte XOR at
                # VPU/HBM speed, no GF matmul (the RAID rebuild path).
                # On CPU backends concrete data drops to the native SIMD
                # XOR via the all-ones row of gf_apply.
                jitted = jax.jit(_xor_reduce_shards)
                ones = np.ones((1, self.k), dtype=np.uint8)

                def fn(data, _jitted=jitted, _ones=ones):
                    from tpu3fs.ops import native_ec, pallas_rs

                    if (not pallas_rs.backend_supports_pallas()
                            and not isinstance(data, jax.core.Tracer)
                            and native_ec.available()):
                        return native_ec.gf_apply(
                            _ones, np.asarray(data))
                    return _jitted(data)
            else:
                R = self._reconstruct_matrix(present, lost)
                R_bits = GF.expand_to_bits(R).astype(np.int8)
                fn = functools.partial(
                    self._apply_bit_matrix, R_bits, key,
                    A_sym=R,
                )
            self._reconstruct_fns[key] = fn
        return fn

    def decode_operand(self, present_idx: Sequence[int],
                       lost_idx: Sequence[int]):
        """The decode of ``lost`` from ``present`` as the (8*lost, 8k) bit
        matrix a device program takes as an OPERAND (cached): laid out for
        the Pallas kernel on a TPU, symbol-major for the einsum elsewhere.
        Every loss pattern that loses as many shards then shares ONE
        compiled program (``apply_operand`` under the caller's jit), where
        a matrix baked into the program would compile once a pattern."""
        from tpu3fs.ops import pallas_rs

        key = (tuple(int(i) for i in present_idx),
               tuple(int(i) for i in lost_idx))
        op = self._decode_operands.get(key)
        if op is None:
            op = GF.expand_to_bits(
                self._reconstruct_matrix(*key)).astype(np.int8)
            if pallas_rs.backend_supports_pallas():
                op = pallas_rs.prepare_matrix(op)
            self._decode_operands[key] = op
        return op

    @staticmethod
    def apply_operand(matrix, data):
        """``decode_operand``'s matrix applied to (..., k, S) survivors ->
        (..., lost, S), for use INSIDE a jitted program: the fused Pallas
        kernel on a TPU, the einsum form elsewhere."""
        from tpu3fs.ops import pallas_rs

        if pallas_rs.backend_supports_pallas():
            return pallas_rs.gf2_matmul(matrix, data)
        return _bit_matmul(matrix, data)

    def _xor_rebuild_applies(self, present, lost) -> bool:
        """True when lost is one shard rebuildable from parity row 0: the
        survivors are exactly the other k-1 data shards + parity 0 (lost
        data shard), or all k data shards (lost parity 0)."""
        if len(lost) != 1 or self.m < 1:
            return False
        (x,) = lost
        if x > self.k:
            return False
        return set(present) == set(range(self.k + 1)) - {x}

    def reconstruct(
        self,
        present_idx: Sequence[int],
        lost_idx: Sequence[int],
        present_shards: jnp.ndarray,
    ) -> jnp.ndarray:
        """Rebuild lost shards from any k surviving shards.

        present_idx: k shard indices in [0, k+m) matching present_shards rows
        present_shards: (..., k, S) uint8
        returns (..., len(lost_idx), S) uint8
        """
        return self.reconstruct_fn(present_idx, lost_idx)(present_shards)

    def reconstruct_np(
        self,
        present_idx: Sequence[int],
        lost_idx: Sequence[int],
        present_shards: np.ndarray,
    ) -> np.ndarray:
        """Gold-path numpy reconstruction."""
        R = self._reconstruct_matrix(
            tuple(int(i) for i in present_idx), tuple(int(i) for i in lost_idx)
        )
        shards = np.asarray(present_shards, dtype=np.uint8)
        *lead, k, s = shards.shape
        flat = shards.reshape(-1, k, s)
        out = np.zeros((flat.shape[0], R.shape[0], s), dtype=np.uint8)
        for i in range(R.shape[0]):
            for j in range(k):
                c = int(R[i, j])
                if c == 0:
                    continue
                if c == 1:
                    out[:, i, :] ^= flat[:, j, :]
                else:
                    out[:, i, :] ^= GF.MUL_TABLE[c][flat[:, j, :]]
        return out.reshape(*lead, R.shape[0], s)

    def __repr__(self) -> str:  # pragma: no cover
        return f"RSCode(k={self.k}, m={self.m})"
