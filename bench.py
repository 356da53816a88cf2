"""RS(12,4) erasure-encode throughput per chip, with the secondary kernel
and served-path rates: measured on a TPU, or a non-zero exit.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device",
...extras}. Baseline: the BASELINE.json north star is >= 40 GiB/s RS(12,4)
encode on a v5e-8 (8 chips), i.e. 5 GiB/s per chip of *data* consumed;
vs_baseline is measured single-chip GiB/s divided by that per-chip share.

A chip belongs to one process at a time, so this orchestrator never imports
JAX: each phase runs in its own child, one child at a time. Every worker
first asserts that JAX's default backend is a TPU and exits non-zero with
"no TPU" otherwise — there is no CPU fallback and no cached result. A
phase that fails or times out fails the run; nothing is reported for it.
Every phase result names the device it ran on (platform, device_kind,
device count). Bit-exactness of the kernels is chip_smoke.py's job.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

K, M = 12, 4
SHARD_BYTES = 1 << 20  # 1 MiB shards (the reference's default chunk size)
BATCH = 12             # 144 MiB of data per step
WARMUP, ITERS = 2, 8
BASELINE_PER_CHIP_GIBPS = 40.0 / 8

HERE = os.path.dirname(os.path.abspath(__file__)) or "."

PHASE_TIMEOUT_S = {            # per-phase budget incl. first compiles
    "headline": 420,
    "secondary": 420,
    "e2e": 600,
    "northstar": 900,
    "e2e_tpu": 600,
}

HEADLINE_METRIC = "rs_encode_12_4_data_throughput_per_chip"


def _gibps(nbytes: int, iters: int, dt: float) -> float:
    return nbytes * iters / dt / (1 << 30)


# --------------------------------------------------------------------------
# phase workers (run in child processes; print one JSON dict on stdout)
# --------------------------------------------------------------------------

def _require_tpu():
    """-> (jax, device dict). The one gate every worker passes first."""
    import jax

    from tpu3fs.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: no TPU (jax's default backend is "
                 f"{dev.platform!r}); nothing was measured")
    return jax, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())}


def _timeit(jax, fn, arg, nbytes: int) -> float:
    for _ in range(WARMUP):
        jax.block_until_ready(fn(arg))
    t0 = time.perf_counter()
    out = None
    for _ in range(ITERS):
        out = fn(arg)
    jax.block_until_ready(out)
    return _gibps(nbytes, ITERS, time.perf_counter() - t0)


def _make_data(jax, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    host = rng.integers(0, 256, (BATCH, K, SHARD_BYTES), dtype=np.uint8)
    return jax.device_put(jnp.asarray(host), jax.devices()[0])


def _phase_headline() -> dict:
    """RS(12,4) encode throughput — the single number that matters."""
    jax, device = _require_tpu()
    from tpu3fs.ops.rs import RSCode

    rs = RSCode(K, M)
    data = _make_data(jax)
    data_bytes = BATCH * K * SHARD_BYTES
    gibps = _timeit(jax, rs.encode, data, data_bytes)
    return {"device": device, "value": round(gibps, 3)}


def _phase_secondary() -> dict:
    """Decode / rebuild / CRC throughput (same data-consumed semantics as
    the headline so the numbers compare)."""
    jax, device = _require_tpu()
    from tpu3fs.ops.crc32c import BatchCrc32c
    from tpu3fs.ops.rs import RSCode

    rs = RSCode(K, M)
    data = _make_data(jax)
    data_bytes = BATCH * K * SHARD_BYTES
    out = {"device": device}
    # worst-case decode: M *data* shards lost (full inverted-submatrix matmul)
    lost = tuple(range(M))
    present = tuple(range(M, K + M))
    decode = rs.reconstruct_fn(present, lost)
    out["rs_decode_worstcase_gibps"] = round(
        _timeit(jax, decode, data, data_bytes), 3)
    # RAID-style 1-loss XOR rebuild (the dominant recovery case)
    xor_present = tuple(i for i in range(K + 1) if i != 1)
    xor_fn = rs.reconstruct_fn(xor_present, (1,))
    out["xor_rebuild_1loss_gibps"] = round(
        _timeit(jax, xor_fn, data, data_bytes), 3)
    # batched CRC32C over all shards
    crc = BatchCrc32c(SHARD_BYTES, block=512)
    flat = data.reshape(BATCH * K, SHARD_BYTES)
    out["crc32c_batch_gibps"] = round(
        _timeit(jax, crc, flat, data_bytes), 3)
    return out


def _phase_e2e() -> dict:
    """Single-process fabric service paths (CRAQ write/read, EC file IO).
    These measure the engine + chain protocol on the chip machine's host,
    not the accelerator; they ride along so regressions in the serving
    path are visible."""
    _, device = _require_tpu()
    from benchmarks.storage_bench import run_bench, run_rpc_bench

    out = {"device": device}
    for eng in ("mem", "native"):
        suffix = "" if eng == "mem" else "_native"
        for row in run_bench(chunks=64, size=256 << 10, batch=8,
                             threads=4, replicas=2, chains=4, engine=eng):
            if "value" in row:  # diagnostic rows carry no headline value
                out[f"e2e_{row['metric']}{suffix}_gibps"] = row["value"]
    # socket-cluster numbers: the full transport (serde envelopes, bulk
    # framing, connection pooling) — python transport on the mem engine,
    # native transport in the flagship config (native engine + C++ read
    # fast path)
    for transport, eng in (("python", "mem"), ("native", "native")):
        suffix = "" if transport == "python" else "_native"
        for row in run_rpc_bench(chunks=64, size=256 << 10, batch=8,
                                 threads=4, replicas=2, chains=4,
                                 transport=transport, engine=eng):
            if "value" in row:
                out[f"e2e_{row['metric']}{suffix}_gibps"] = row["value"]

    from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
    from tpu3fs.meta.store import OpenFlags

    ec_chunk = 256 << 10
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=4, num_chains=2, chunk_size=ec_chunk,
        ec_k=3, ec_m=1))
    stripes = 32
    blobs = [bytes([i & 0xFF]) * ec_chunk for i in range(4)]
    fio = fab.file_client()
    payload = b"".join(blobs[i % 4] for i in range(stripes))
    # warm the lazy one-time costs (codec/native-lib/table init) so the
    # measurement is the serving path, not first-use initialization
    warm = fab.meta.create("/ecwarm", flags=OpenFlags.WRITE,
                           client_id="bench")
    fio.write(warm.inode, 0, payload[: 4 * ec_chunk])
    res = fab.meta.create("/ecbench", flags=OpenFlags.WRITE,
                          client_id="bench")
    t0 = time.perf_counter()
    fio.write(res.inode, 0, payload)
    out["e2e_ec_write_gibps"] = round(
        _gibps(stripes * ec_chunk, 1, time.perf_counter() - t0), 3)
    t0 = time.perf_counter()
    fio.write(res.inode, 0, payload)
    out["e2e_ec_overwrite_gibps"] = round(
        _gibps(stripes * ec_chunk, 1, time.perf_counter() - t0), 3)
    t0 = time.perf_counter()
    back = fio.read(res.inode, 0, stripes * ec_chunk)
    dt = time.perf_counter() - t0
    assert back == payload, "EC file read-back mismatch"
    out["e2e_ec_read_gibps"] = round(_gibps(stripes * ec_chunk, 1, dt), 3)
    return out


def _phase_northstar() -> dict:
    """BASELINE.md's headline workloads, scaled to the bench budget:
    GraySort-style shuffle (solver-validated placement + device-sorted
    range partitioning + batched write-back), KVCache 128 KiB random
    reads racing a TTL GC on RS(12,4), and a sized failed-node EC
    rebuild. Sizes via TPU3FS_NS_* env knobs (northstar_bench)."""
    _, device = _require_tpu()
    from benchmarks.northstar_bench import run_all

    return {"device": device, **run_all()}


def _phase_e2e_tpu() -> dict:
    """EC serving path with the DEVICE data plane: fabric write/read and a
    failed-node rebuild where stripe encode + CRC32C run on the accelerator
    (TPU3FS_STRIPE_DEVICE=1 forces the device path that stripe.py otherwise
    reserves for device-resident data). RS(12,4) / 1 MiB stripes to match
    the BASELINE.json KVCache config. Every stripe batch pays a
    host->device round trip and the number includes it. The fabric is one
    process, so the one chip has one owner."""
    os.environ["TPU3FS_STRIPE_DEVICE"] = "1"
    _, device = _require_tpu()
    out = {"device": device}

    from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
    from tpu3fs.meta.store import OpenFlags

    stripe = 1 << 20
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=4, num_chains=2, chunk_size=stripe,
        ec_k=12, ec_m=4))
    try:
        stripes = 48  # 48 MiB of file data per measured pass
        payload = b"".join(
            bytes([i & 0xFF]) * stripe for i in range(stripes))
        fio = fab.file_client()
        # full-size warmup: compiles the exact shape buckets (encode, CRC)
        # the measured pass will hit, plus codec/table init
        warm = fab.meta.create("/warm", flags=OpenFlags.WRITE,
                               client_id="bench")
        fio.write(warm.inode, 0, payload)
        fio.read(warm.inode, 0, len(payload))
        res = fab.meta.create("/tpubench", flags=OpenFlags.WRITE,
                              client_id="bench")
        t0 = time.perf_counter()
        fio.write(res.inode, 0, payload)
        out["e2e_tpu_ec_write_gibps"] = round(
            _gibps(len(payload), 1, time.perf_counter() - t0), 3)
        t0 = time.perf_counter()
        back = fio.read(res.inode, 0, len(payload))
        dt = time.perf_counter() - t0
        assert back == payload, "EC read-back mismatch on device data plane"
        out["e2e_tpu_ec_read_gibps"] = round(_gibps(len(payload), 1, dt), 3)
        # failed-node rebuild: every shard that node held is re-derived on
        # device from surviving shards (the BASELINE.md rebuild workload,
        # scaled to the bench budget)
        victim = sorted(fab.nodes)[0]
        lost_bytes = sum(
            t.engine.used_size() for t in fab.nodes[victim].service.targets())
        fab.fail_node(victim)
        t0 = time.perf_counter()
        fab.restart_node(victim)
        fab.resync_all(rounds=6)
        out["e2e_tpu_rebuild_gibps"] = round(
            _gibps(lost_bytes, 1, time.perf_counter() - t0), 3)
        out["e2e_tpu_rebuild_bytes"] = lost_bytes
    finally:
        fab.close()
    return out


_PHASE_FNS = {
    "headline": _phase_headline,
    "secondary": _phase_secondary,
    "e2e": _phase_e2e,
    "northstar": _phase_northstar,
    "e2e_tpu": _phase_e2e_tpu,
}


# --------------------------------------------------------------------------
# orchestrator (never imports jax)
# --------------------------------------------------------------------------

def _run_phase(phase: str) -> dict:
    """Run one phase in a bounded child. Any failure ends the run."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker", phase],
            capture_output=True, text=True,
            timeout=PHASE_TIMEOUT_S[phase], cwd=HERE,
        )
    except subprocess.TimeoutExpired:
        sys.exit(f"bench: phase {phase} exceeded {PHASE_TIMEOUT_S[phase]}s")
    lines = [ln for ln in out.stdout.strip().splitlines()
             if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        sys.stderr.write((out.stderr or out.stdout)[-2000:])
        sys.exit(f"bench: phase {phase} failed (rc={out.returncode})")
    return json.loads(lines[-1])


def main() -> None:
    rec: dict = {}
    for phase in _PHASE_FNS:
        res = _run_phase(phase)
        device = res.pop("device")
        if phase == "headline":
            rec = {
                "metric": HEADLINE_METRIC,
                "value": res["value"],
                "unit": "GiB/s",
                "vs_baseline": round(
                    res["value"] / BASELINE_PER_CHIP_GIBPS, 3),
                "device": device,
            }
        else:
            assert device == rec["device"], (phase, device, rec["device"])
            rec.update(res)
    print(json.dumps(rec))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        print(json.dumps(_PHASE_FNS[sys.argv[2]]()))
    else:
        main()
