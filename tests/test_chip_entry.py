"""What stands between a run without the chip and a result that looks like
one: the chip-or-fail entry points, the one-owner child environment, the
placeable compile cache, and the codec's device branches.

The device branches are driven here on jax-cpu with ``_host_mode`` forced
off (the environment switch itself refuses a non-TPU backend, which is
tested too); chip_smoke.py drives them on the chip.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, cwd=REPO,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_bare_run_without_a_tpu_fails_and_reports_nothing(script):
    out = _run([script])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    # no result object, under any name
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_smoke_children_are_pinned_to_the_cpu(monkeypatch):
    import chip_smoke

    monkeypatch.setenv("TPU3FS_STRIPE_DEVICE", "1")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = chip_smoke.child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU3FS_STRIPE_DEVICE" not in env
    assert env["PYTHONPATH"].split(os.pathsep)[0] == REPO


def test_smoke_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond ok and device
    (platform, kind, count); sections and seed go on the lines before."""
    import json

    import chip_smoke

    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    got = json.loads(line)
    assert list(got) == ["ok", "device"] and got["ok"] is True
    assert got["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}
    assert type(got["device"]["count"]) is int


def test_compile_cache_dir_is_placeable_and_otherwise_fixed(
        monkeypatch, tmp_path):
    from tpu3fs.utils import compile_cache as cc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert cc.compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = cc.compile_cache_dir()
    monkeypatch.chdir(tmp_path)
    assert cc.compile_cache_dir() == first == os.path.join(REPO, ".jax_cache")


def test_enable_compile_cache_sets_no_path_over_the_environment(tmp_path):
    """In a fresh, unpinned process (no backend comes up): with the
    variable set JAX keeps its own reading of it; unset, the in-checkout
    path is configured, from any working directory."""
    code = ("import jax\n"
            "from tpu3fs.utils.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": ""}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for given, want in (("/some/dir", "/some/dir"),
                        (None, os.path.join(REPO, ".jax_cache"))):
        if given:
            env["JAX_COMPILATION_CACHE_DIR"] = given
        else:
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=tmp_path, capture_output=True, text=True,
                             timeout=120)
        assert out.stdout.split() == [want, want], out.stderr[-500:]
    assert not os.path.exists(os.path.join(REPO, ".jax_cache"))


def test_cpu_pinned_process_gets_no_compile_cache():
    from tpu3fs.utils.compile_cache import enable_compile_cache

    assert enable_compile_cache() is None  # conftest pins the cpu


def test_backend_init_error_propagates(monkeypatch):
    import jax

    from tpu3fs.ops import pallas_rs
    from tpu3fs.ops.rs import RSCode

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu': busy")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pallas_rs.backend_supports_pallas()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        RSCode(3, 1).encode(np.zeros((1, 3, 64), dtype=np.uint8))


def test_stripe_device_switch_refuses_a_non_tpu_backend(monkeypatch):
    from tpu3fs.ops.stripe import StripeCodec

    monkeypatch.setenv("TPU3FS_STRIPE_DEVICE", "1")
    with pytest.raises(RuntimeError, match="no TPU"):
        StripeCodec(3, 1, 512).encode_batch(
            np.zeros((1, 3, 512), dtype=np.uint8))
    monkeypatch.delenv("TPU3FS_STRIPE_DEVICE")
    assert StripeCodec(3, 1, 512)._use_host()


# (3, 1, 5632): a 4-MiB chunk's shard (S = 1 398 272) scaled by 1/256,
# shard_size_of(16 KiB, 3): the same uneven split, padded to the 512-B grid
@pytest.mark.parametrize("k,m,S", [(3, 1, 1024), (12, 4, 512), (2, 1, 64),
                                   (3, 1, 5632)])
def test_codec_device_branches_match_host_past_the_batch_bound(
        monkeypatch, k, m, S):
    from tpu3fs.ops import stripe

    # the bound is in bytes; shrink it so a small batch spans several
    # dispatches plus a ragged, bucket-padded tail
    monkeypatch.setattr(stripe, "DEVICE_BATCH_BYTES", 16 * (k + m) * S)
    host, dev = stripe.StripeCodec(k, m, S), stripe.StripeCodec(k, m, S)
    host._host_mode, dev._host_mode = True, False
    assert dev._device_step(k + m) == 16
    rng = np.random.default_rng(k * 100 + m)
    batch = 2 * 16 + 5
    data = rng.integers(0, 256, (batch, k, S), dtype=np.uint8)

    shards, crcs = host.encode_batch(data)
    d_shards, d_crcs = dev.encode_batch(data)
    assert np.array_equal(d_shards, shards) and np.array_equal(d_crcs, crcs)
    parity, p_crcs = dev.encode_parity(data)
    assert np.array_equal(parity, shards[:, k:])
    assert np.array_equal(p_crcs, crcs)

    lost, present = tuple(range(m)), tuple(range(m, k + m))
    surv = shards[:, list(present)]
    want = host.reconstruct_batch(present, lost, surv)
    assert np.array_equal(want, data[:, list(lost)])
    assert np.array_equal(dev.reconstruct_batch(present, lost, surv), want)

    rows = shards.reshape(-1, S)
    assert np.array_equal(dev.crc_batch(rows), host.crc_batch(rows))
    assert np.array_equal(dev.crc_batch(rows[:1]), host.crc_batch(rows[:1]))


def test_prepared_matrix_is_concrete_even_when_built_under_a_trace():
    """On a TPU the codec's jitted step traces RSCode.encode, which caches
    its prepared matrix on first use. Two batch buckets are two traces: the
    second found the first one's tracer in that cache (on the chip, the
    first time a served write followed the kernel checks). What gets
    cached must be a concrete array whoever asks first."""
    import jax

    from tpu3fs.ops import pallas_rs
    from tpu3fs.ops.rs import RSCode

    rs = RSCode(3, 1)
    seen = {}

    def step(x):
        seen["A"] = pallas_rs.prepare_matrix(rs._parity_bits)
        return x + 1

    jax.jit(step)(np.int32(1))
    assert not isinstance(seen["A"], jax.core.Tracer)
    assert np.array_equal(
        np.asarray(seen["A"]), pallas_rs._to_plane_major(rs._parity_bits))
