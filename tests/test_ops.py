"""Kernel gold tests: GF(2^8), RS(k,m), CRC32C (bit-exact vs known vectors).

Mirrors the reference's strategy of validating checksum paths against known
implementations (folly::crc32c there; standard vectors here).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu3fs.ops.gf256 import GF
from tpu3fs.ops.rs import RSCode
from tpu3fs.ops.crc32c import BatchCrc32c, crc32c, crc32c_combine


class TestGF:
    def test_mul_identity_zero(self):
        a = np.arange(256, dtype=np.uint8)
        assert np.array_equal(GF.mul(a, 1), a)
        assert np.array_equal(GF.mul(a, 0), np.zeros(256, dtype=np.uint8))

    def test_mul_commutative_associative(self):
        rng = np.random.default_rng(0)
        a, b, c = rng.integers(0, 256, (3, 64)).astype(np.uint8)
        assert np.array_equal(GF.mul(a, b), GF.mul(b, a))
        assert np.array_equal(GF.mul(GF.mul(a, b), c), GF.mul(a, GF.mul(b, c)))

    def test_distributive_over_xor(self):
        rng = np.random.default_rng(1)
        a, b, c = rng.integers(0, 256, (3, 64)).astype(np.uint8)
        assert np.array_equal(GF.mul(a, b ^ c), GF.mul(a, b) ^ GF.mul(a, c))

    def test_inverse(self):
        for x in range(1, 256):
            assert int(GF.mul(x, GF.inv(x))) == 1

    def test_mat_inv(self):
        rng = np.random.default_rng(2)
        for _ in range(5):
            n = 6
            while True:
                A = rng.integers(0, 256, (n, n)).astype(np.uint8)
                try:
                    Ainv = GF.mat_inv(A)
                    break
                except np.linalg.LinAlgError:
                    continue
            assert np.array_equal(GF.matmul(A, Ainv), np.eye(n, dtype=np.uint8))

    def test_cauchy_mds(self):
        # any k rows of [I; C] must be invertible
        k, m = 4, 3
        gen = np.concatenate(
            [np.eye(k, dtype=np.uint8), GF.cauchy_parity_matrix(m, k)], axis=0
        )
        import itertools

        for rows in itertools.combinations(range(k + m), k):
            GF.mat_inv(gen[list(rows), :])  # raises if singular

    def test_const_bit_matrix(self):
        # bit matrix of c applied to bits of x == bits of mul(c, x)
        rng = np.random.default_rng(3)
        for _ in range(20):
            c = int(rng.integers(0, 256))
            x = int(rng.integers(0, 256))
            M = GF.const_bit_matrix(c)
            xb = ((x >> np.arange(8)) & 1).astype(np.uint8)
            yb = (M.astype(np.int64) @ xb.astype(np.int64)) & 1
            y = int((yb << np.arange(8)).sum())
            assert y == int(GF.mul(c, x))


class TestRS:
    @pytest.mark.parametrize("k,m", [(3, 1), (3, 2), (8, 2), (12, 4)])
    def test_encode_matches_gold(self, k, m):
        rng = np.random.default_rng(42)
        rs = RSCode(k, m)
        data = rng.integers(0, 256, (2, k, 256)).astype(np.uint8)
        gold = rs.encode_np(data)
        got = np.asarray(rs.encode(data))
        assert np.array_equal(got, gold)

    @pytest.mark.parametrize("k,m", [(3, 2), (12, 4)])
    def test_reconstruct_any_m_erasures(self, k, m):
        import itertools

        rng = np.random.default_rng(7)
        rs = RSCode(k, m)
        data = rng.integers(0, 256, (1, k, 128)).astype(np.uint8)
        parity = rs.encode_np(data)
        shards = np.concatenate([data, parity], axis=1)  # (1, k+m, S)
        combos = list(itertools.combinations(range(k + m), m))
        rng.shuffle(combos)
        for lost in combos[:10]:
            present = tuple(i for i in range(k + m) if i not in lost)[:k]
            rebuilt = np.asarray(
                rs.reconstruct(present, lost, shards[:, list(present), :])
            )
            assert np.array_equal(rebuilt, shards[:, list(lost), :]), (lost, present)

    def test_reconstruct_gold_matches_jax(self):
        rs = RSCode(4, 2)
        rng = np.random.default_rng(9)
        data = rng.integers(0, 256, (3, 4, 64)).astype(np.uint8)
        parity = rs.encode_np(data)
        shards = np.concatenate([data, parity], axis=1)
        present, lost = (0, 2, 4, 5), (1, 3)
        np_out = rs.reconstruct_np(present, lost, shards[:, list(present), :])
        jx_out = np.asarray(rs.reconstruct(present, lost, shards[:, list(present), :]))
        assert np.array_equal(np_out, jx_out)

    def test_zero_data_zero_parity(self):
        rs = RSCode(5, 3)
        data = np.zeros((1, 5, 32), dtype=np.uint8)
        assert not np.asarray(rs.encode(data)).any()


class TestCrc32c:
    def test_known_vectors(self):
        # Standard CRC32C test vectors
        assert crc32c(b"") == 0
        assert crc32c(b"123456789") == 0xE3069283
        assert crc32c(b"\x00" * 32) == 0x8A9136AA
        assert crc32c(b"\xff" * 32) == 0x62A8AB43

    def test_chaining(self):
        data = b"hello world, this is tpu3fs"
        assert crc32c(data[10:], crc32c(data[:10])) == crc32c(data)

    def test_combine(self):
        rng = np.random.default_rng(11)
        a = rng.integers(0, 256, 1000).astype(np.uint8).tobytes()
        b = rng.integers(0, 256, 777).astype(np.uint8).tobytes()
        assert crc32c_combine(crc32c(a), crc32c(b), len(b)) == crc32c(a + b)
        assert crc32c_combine(crc32c(a), crc32c(b""), 0) == crc32c(a)

    @pytest.mark.parametrize("size,block", [(512, 512), (4096, 512), (8192, 1024)])
    def test_batch_matches_scalar(self, size, block):
        rng = np.random.default_rng(13)
        batch = 4
        chunks = rng.integers(0, 256, (batch, size)).astype(np.uint8)
        bc = BatchCrc32c(size, block)
        got = np.asarray(bc(chunks))
        want = np.array([crc32c(chunks[i].tobytes()) for i in range(batch)],
                        dtype=np.uint32)
        assert np.array_equal(got, want)

    def test_batch_zero_and_ones(self):
        size = 1024
        bc = BatchCrc32c(size, 256)
        chunks = np.stack(
            [np.zeros(size, dtype=np.uint8), np.full(size, 0xFF, dtype=np.uint8)]
        )
        got = np.asarray(bc(chunks))
        assert got[0] == crc32c(b"\x00" * size)
        assert got[1] == crc32c(b"\xff" * size)


class TestRSXorFastPath:
    """The normalized generator (parity row 0 all-ones) and its consequences."""

    def test_parity_row0_is_xor(self):
        import functools as ft

        rs = RSCode(12, 4)
        assert (rs.parity_matrix[0] == 1).all()
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, (2, 12, 256), dtype=np.uint8)
        parity = rs.encode_np(data)
        assert (parity[:, 0, :] ==
                ft.reduce(np.bitwise_xor, [data[:, j] for j in range(12)])).all()

    def test_mds_all_single_and_sampled_multi_losses(self):
        """Column-normalizing the Cauchy matrix must keep the code MDS."""
        import itertools

        rs = RSCode(6, 3)
        rng = np.random.default_rng(4)
        data = rng.integers(0, 256, (6, 64), dtype=np.uint8)
        shards = np.concatenate([data, rs.encode_np(data)], axis=0)
        n = rs.k + rs.m
        patterns = [c for r in range(1, rs.m + 1)
                    for c in itertools.combinations(range(n), r)]
        for lost in patterns:
            present = tuple(i for i in range(n) if i not in lost)[: rs.k]
            out = rs.reconstruct_np(present, lost, shards[list(present)])
            assert (out == shards[list(lost)]).all(), f"lost={lost}"

    def test_xor_path_matches_general_decode(self):
        rs = RSCode(8, 2)
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, (8, 128), dtype=np.uint8)
        shards = np.concatenate([data, rs.encode_np(data)], axis=0)
        # lose data shard 3: survivors = other data + parity0
        present = tuple(i for i in range(9) if i != 3)
        fn = rs.reconstruct_fn(present, (3,))
        assert rs._xor_rebuild_applies(present, (3,))
        out = np.asarray(fn(jnp.asarray(shards[list(present)])))
        assert (out[0] == data[3]).all()
        # same answer as the numpy gold GF decode
        gold = rs.reconstruct_np(present, (3,), shards[list(present)])
        assert (out == gold).all()
        # lose parity0: xor of all data
        present = tuple(range(8))
        fn = rs.reconstruct_fn(present, (8,))
        assert rs._xor_rebuild_applies(present, (8,))
        out = np.asarray(fn(jnp.asarray(shards[list(present)])))
        assert (out[0] == shards[8]).all()

    def test_xor_path_not_applied_when_pattern_disallows(self):
        rs = RSCode(8, 2)
        assert not rs._xor_rebuild_applies(tuple(range(1, 9)), (0, 9))
        assert not rs._xor_rebuild_applies((0, 1, 2, 3, 4, 5, 6, 9), (7,))


class TestPallasKernel:
    """Fused GF(2) matmul kernel vs the einsum/gold paths (interpret mode
    so the kernel logic runs in CPU CI; the real lowering is exercised on
    TPU by chip_smoke.py)."""

    def test_encode_bit_exact(self):
        from tpu3fs.ops.pallas_rs import gf2_matmul, prepare_matrix

        rs = RSCode(5, 3)
        rng = np.random.default_rng(6)
        data = rng.integers(0, 256, (2, 5, 640), dtype=np.uint8)
        A = prepare_matrix(np.asarray(rs._parity_bits))
        out = np.asarray(gf2_matmul(A, jnp.asarray(data), interpret=True,
                                    block_s=256))
        assert (out == rs.encode_np(data)).all()

    def test_padding_and_2d_input(self):
        from tpu3fs.ops.pallas_rs import gf2_matmul, prepare_matrix

        rs = RSCode(4, 2)
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, (4, 300), dtype=np.uint8)  # S not /128
        A = prepare_matrix(np.asarray(rs._parity_bits))
        out = np.asarray(gf2_matmul(A, jnp.asarray(data), interpret=True,
                                    block_s=256))
        assert (out == rs.encode_np(data)).all()


class TestNativeEc:
    """Native SIMD GF/CRC (native/chunk_engine.cpp ce_gf_apply /
    ce_crc32c_batch) vs the numpy gold path — the CPU-backend serving
    kernels (round-3 verdict ask #2)."""

    def test_available(self):
        from tpu3fs.ops import native_ec

        assert native_ec.available()

    def test_encode_matches_gold_random_codes(self):
        from tpu3fs.ops import native_ec

        rng = np.random.default_rng(0)
        for k, m in ((3, 1), (4, 2), (12, 4), (1, 1), (8, 3)):
            rs = RSCode(k, m)
            # sizes straddle the 16/32-byte SIMD strides and the scalar tail
            for s in (17, 32, 100, 512, 4096):
                data = rng.integers(0, 256, (2, k, s), dtype=np.uint8)
                got = native_ec.gf_apply(rs.parity_matrix, data)
                assert np.array_equal(got, rs.encode_np(data)), (k, m, s)

    def test_decode_matches_gold(self):
        from tpu3fs.ops import native_ec

        rs = RSCode(6, 3)
        rng = np.random.default_rng(1)
        data = rng.integers(0, 256, (3, 6, 333), dtype=np.uint8)
        shards = np.concatenate([data, rs.encode_np(data)], axis=1)
        present = (0, 2, 4, 6, 7, 8)
        lost = (1, 3, 5)
        R = rs._reconstruct_matrix(present, lost)
        got = native_ec.gf_apply(R, shards[:, list(present)])
        assert np.array_equal(got, data[:, list(lost)])

    def test_crc_batch_matches_scalar(self):
        from tpu3fs.ops import native_ec
        from tpu3fs.ops.crc32c import crc32c_py

        rng = np.random.default_rng(2)
        for s in (1, 7, 64, 1000):
            rows = rng.integers(0, 256, (5, s), dtype=np.uint8)
            got = native_ec.crc32c_batch(rows)
            want = [crc32c_py(r.tobytes()) for r in rows]
            assert list(got) == want, s

    def test_cpu_backend_apis_route_native_and_stay_bit_exact(self):
        # RSCode.encode / BatchCrc32c.__call__ / reconstruct_fn on the CPU
        # backend must return the same bits as the gold path regardless of
        # which kernel they picked
        rs = RSCode(5, 2)
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, (2, 5, 512), dtype=np.uint8)
        assert np.array_equal(np.asarray(rs.encode(jnp.asarray(data))),
                              rs.encode_np(data))
        shards = np.concatenate([data, rs.encode_np(data)], axis=1)
        # xor fast path (lost data shard 1, survivors 0,2,3,4 + parity 0)
        fn = rs.reconstruct_fn((0, 2, 3, 4, 5), (1,))
        got = np.asarray(fn(jnp.asarray(shards[:, [0, 2, 3, 4, 5]])))
        assert np.array_equal(got, data[:, [1]])
        from tpu3fs.ops.crc32c import BatchCrc32c, crc32c

        crc = BatchCrc32c(512, block=512)
        got_crc = np.asarray(crc(jnp.asarray(data.reshape(-1, 512))))
        want = [crc32c(r.tobytes()) for r in data.reshape(-1, 512)]
        assert list(got_crc) == want
