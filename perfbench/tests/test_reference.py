"""The plain reference against the program it shares no code with."""

import numpy as np
import pytest

from perfbench.lib import reference as ref


@pytest.mark.parametrize("k,m,s,seed", [(12, 4, 1024, 1), (12, 4, 87552, 2),
                                        (8, 2, 512, 3), (3, 1, 192, 4)])
def test_parity_agrees_with_the_programs_gold(k, m, s, seed):
    from tpu3fs.ops.rs import RSCode

    data = np.random.default_rng(seed).integers(0, 256, (k, s),
                                                dtype=np.uint8)
    assert np.array_equal(ref.rs_parity(data, m),
                          RSCode(k, m).encode_np(data[None])[0])


def test_reference_shares_no_code_with_the_program():
    import inspect

    src = inspect.getsource(ref)
    assert "import tpu3fs" not in src and "from tpu3fs" not in src


def test_gf_arithmetic():
    assert ref.gf_mul(2, 0x80) == 0x1D          # x * x^7 = x^8 = poly tail
    assert ref.gf_mul(7, 1) == 7 and ref.gf_mul(0, 9) == 0
    for a in (1, 2, 3, 0x53, 0xFF):
        assert ref.gf_mul(a, ref.gf_inv(a)) == 1
    assert ref.parity_matrix(12, 4)[0] == [1] * 12


def test_shard_size_and_stripe_shards():
    from tpu3fs.ops.stripe import shard_size_of

    for cs, k in ((1 << 20, 12), (1 << 20, 8), (4096, 2), (100, 3)):
        assert ref.shard_size(cs, k) == shard_size_of(cs, k)
    chunk = bytes(range(256)) * 10            # 2560 B of a 4096 B chunk
    shards = ref.stripe_shards(chunk, 4096, 3, 1)
    s = ref.shard_size(4096, 3)
    assert [len(x) for x in shards] == [s, 2560 - s, 0, s]
    assert b"".join(shards[:3]) == chunk


def test_kvcache_format_matches_the_programs():
    from tpu3fs.kvcache import chain_keys, encode_array, shard_path

    tokens = np.random.default_rng(5).integers(0, 1 << 40, 200).tolist()
    assert ref.chain_keys(tokens, 64) == chain_keys(tokens, 64)
    key = ref.chain_keys(tokens, 64)[1]
    assert ref.entry_path("/kv/x", key) == shard_path("/kv/x", key)
    arr = np.arange(8 * 64 * 576, dtype=np.uint16).reshape(8, 64, 576)
    assert ref.encode_entry(arr) == encode_array(arr)


@pytest.mark.parametrize("dtype", ["uint16", "float32", "bfloat16"])
def test_fingerprint_twins_agree(dtype):
    import jax.numpy as jnp

    from perfbench.lib.device import fingerprint

    x = np.random.default_rng(6).standard_normal((33, 17)).astype(np.float32)
    xj = jnp.asarray(x).astype(dtype) if dtype != "uint16" else \
        jnp.asarray((x * 1000).astype(np.int32) % 65536, dtype=jnp.uint16)
    want = ref.fingerprint_np(ref.as_unsigned(np.asarray(xj)))
    assert tuple(int(v) for v in np.asarray(fingerprint(xj))) == want
    y = np.asarray(xj).copy().reshape(-1)
    y[[3, 4]] = y[[4, 3]]                      # a swap keeps the plain sum
    if y[3] != y[4]:
        swapped = ref.fingerprint_np(ref.as_unsigned(y))
        assert swapped[0] == want[0] and swapped[1] != want[1]


def test_fixed_sizes_and_apportionment():
    sizes = ref.quantiles_lognormal(32, 1024, 1.0, 256, 8192, 64)
    assert sizes == sorted(sizes) and sizes[0] == 256 and sizes[-1] == 8192
    assert all(x % 64 == 0 for x in sizes)
    counts = ref.apportion([1 / (i + 1) for i in range(32)], 128)
    assert sum(counts) == 128 and min(counts) >= 1 and counts[0] == 32


def test_crc32c_is_the_castagnoli_crc():
    assert ref.crc32c(b"123456789") == 0xE3069283   # the check value
    assert ref.crc32c(b"") == 0
    # bit by bit, by the definition (reflected polynomial 0x82F63B78)
    data = np.random.default_rng(7).integers(0, 256, 1000, dtype=np.uint8)
    crc = 0xFFFFFFFF
    for byte in data.tolist():
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
    assert ref.crc32c(data.tobytes()) == crc ^ 0xFFFFFFFF


def test_record_file_matches_the_programs_oracle():
    from tpu3fs.dataload.recordio import encode_record_file

    records = np.random.default_rng(8).integers(
        0, 1 << 31, (37, 256), dtype=np.int32)
    image = encode_record_file([r.tobytes() for r in records])
    head = ref.record_file_head(records)
    assert len(head) == 32 + 16 * 37
    assert ref.record_file_bytes(head, records, 0, len(image)) == image
    for lo, hi in ((0, 100), (500, 700), (len(head) - 3, len(head) + 5),
                   (4096, 8192), (len(image) - 10, len(image))):
        assert ref.record_file_bytes(head, records, lo, hi) == image[lo:hi]


def test_ckpt_shard_file_matches_the_programs_naming():
    from tpu3fs.ckpt.manifest import shard_file_name

    assert ref.ckpt_shard_file(17) == shard_file_name(17, 0) == "l17.s0"
