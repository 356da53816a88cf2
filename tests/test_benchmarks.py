"""Smoke tests for the benchmark harnesses (tiny configurations).

The reference treats its benches as part of the tree (benchmarks/
storage_bench reuses UnitTestFabric; the fio plugin builds in CI) — these
keep ours importable and correct without measuring anything."""

from benchmarks.ckpt_bench import run_bench as ckpt_bench
from benchmarks.dataload_bench import run_bench as dataload_bench
from benchmarks.rebuild_bench import run_bench as rebuild_bench
from benchmarks.storage_bench import run_bench as storage_bench
from benchmarks.usrbio_bench import run_bench as usrbio_bench


class TestStorageBench:
    def test_small_run_with_verify(self):
        rows = storage_bench(chunks=16, size=4096, batch=4, threads=2,
                             replicas=2, chains=2, verify=True)
        names = [r["metric"] for r in rows]
        assert names == ["storage_bench_write", "storage_bench_read",
                         "storage_bench_batch_read",
                         "storage_bench_batch_write",
                         "storage_bench_write_decomp"]
        assert all(r["value"] > 0 for r in rows if "value" in r)
        assert rows[0]["ops"] == 16
        # the decomposition must account for the batched writes it saw
        decomp = rows[-1]
        assert decomp["ops"] == 16
        assert decomp["head_wall_s"] > 0
        # components never exceed the wall they decompose
        assert (decomp["head_stage_s"] + decomp["forward_msg_s"]
                + decomp["head_commit_s"]) <= decomp["head_wall_s"] + 0.01

    def test_error_injection_still_completes(self):
        rows = storage_bench(chunks=8, size=4096, batch=4, threads=2,
                             replicas=2, chains=1, inject=0.3, verify=True)
        assert rows[0]["ops"] == 8  # retries absorb the injected faults


class TestUsrbioBench:
    def test_small_run(self):
        # tiny in-process A/B: both transports produce data, every
        # metric row carries ring + sock samples and a speedup
        rows = usrbio_bench(chunk_kb=64, batch=4, reps=1, single_ops=2,
                            iov_mb=16, inproc=True)
        names = {r["metric"] for r in rows}
        assert names == {"usrbio_batch_read", "usrbio_batch_write",
                         "usrbio_wire_read", "usrbio_wire_write",
                         "usrbio_single_read_us",
                         "usrbio_single_write_us"}
        for r in rows:
            assert r["ring"] > 0 and r["sock"] > 0
            assert len(r["samples_ring"]) == 1
            assert r["speedup"] > 0
            # reruns on other hosts must be able to judge core-bound
            # numbers: every row records the cores it ran on
            assert r["host_cpus"] >= 1


class TestRebuildBench:
    def test_small_run(self):
        rows = rebuild_bench(k=4, m=2, shard_kb=16, batch=2, iters=2,
                             pod_chips=8)
        assert len(rows) == 2
        assert rows[0]["metric"] == "rs_rebuild_4_2_lost1"
        assert all(r["value"] > 0 for r in rows)


class TestCkptBench:
    """Fast-mode smoke of benchmarks/ckpt_bench: every reported metric
    present and positive, data verified inside the bench itself."""

    def test_small_run(self):
        row = ckpt_bench(total_mb=1, leaves=2, nodes=2, chains=2,
                         replicas=2, ec_k=2, ec_m=1, reshard=True)
        assert row["value"] > 0
        for label in ("cr", "ec2_1"):
            assert row[f"{label}_save_gibps"] > 0
            assert row[f"{label}_restore_gibps"] > 0
            assert row[f"{label}_restore_ranged_gibps"] > 0
            assert row[f"{label}_bytes"] == 1 << 20
            # the async stall is the snapshot only: it must not exceed
            # the full sync save wall (generous 2x slack for CI noise)
            assert row[f"{label}_async_step_stall_ms"] <= \
                row[f"{label}_sync_save_ms"] * 2.0 + 5.0
        assert row["cr_reshard_restore_gibps"] > 0


class TestDataloadBench:
    """benchmarks/dataload_bench fast-mode smoke: the harness runs over
    real sockets, every reported field lands, data is verified inside
    (per-record CRC), and resume-from-state is EXACT."""

    def test_small_run(self):
        row = dataload_bench(total_mb=1, record_kbs=(16,), batch=8,
                             depth=2, chains=2, replicas=2)
        p = "r16k"
        assert row["value"] > 0
        assert row[f"{p}_records"] >= 64
        assert row[f"{p}_naive_samples_s"] > 0
        assert row[f"{p}_shuffled_samples_s"] > 0
        assert row[f"{p}_seq_samples_s"] > 0
        assert row[f"{p}_train_samples_s"] > 0
        assert row[f"{p}_resume_exact"] is True
        for d in (1, 2, 4):
            assert row[f"{p}_depth{d}_samples_s"] > 0


class TestKvcacheBench:
    """benchmarks/kvcache_bench fast-mode smoke: runs over real sockets,
    every reported field lands, block data verified inside the bench,
    host-tier hits proven storage-RPC-free by the harness assert."""

    def test_small_run(self):
        from benchmarks.kvcache_bench import run_bench as kvcache_bench

        row = kvcache_bench(blocks=8, block_kb=16, chains=2, replicas=2,
                            gc_entries=8)
        assert row["value"] > 0
        for key in ("put_gibps", "naive_get_gibps", "block_get_gibps",
                    "tier_fill_gibps", "host_hit_gibps", "host_get_us",
                    "fs_get_us", "gc_remove_iops"):
            assert row[key] > 0, key
        assert row["host_hit_storage_rpcs"] == 0
        assert row["block_speedup_vs_naive"] > 0
        # 6 of 8 blocks shared at the 3/4 prefix point; session B wrote
        # exactly the unshared tail
        assert row["prefix_shared_blocks"] == 6
        assert row["session_b_blocks_written"] == 2
        assert row["gc_removed"] >= 8


class TestReadBench:
    """benchmarks/read_bench fast-mode smoke: the matrix runs, every cell
    reports, prefetch rows carry their hit/miss accounting."""

    def test_python_matrix_smoke(self):
        from benchmarks.read_bench import run

        rows = run(chunks=8, size=16 << 10, batch=4, replicas=2, chains=2,
                   rounds=1, transports=("python",))
        names = [r["metric"] for r in rows]
        assert names == ["readpath_single", "readpath_batch",
                         "readpath_striped", "readpath_prefetch_off",
                         "readpath_prefetch_on"]
        assert all(r.get("value", 0) > 0 for r in rows)
        on = rows[-1]
        assert on["prefetch_hits"] + on["prefetch_misses"] > 0


class TestWriteBench:
    """benchmarks/write_bench fast-mode smoke: the full mode matrix over
    real sockets (python transport; native is exercised in its own
    tier-2 runs), pre-PR inline baseline included, speedup row present."""

    def test_small_run(self):
        from benchmarks.write_bench import run as write_bench

        rows = write_bench(chunks=8, size=32 << 10, batch=4, rounds=1,
                           chains=2, replicas=2, transports=("python",))
        by = {r["metric"]: r for r in rows if "value" in r}
        for m in ("writepath_single", "writepath_batch_nopipe",
                  "writepath_batch", "writepath_striped"):
            assert by[m]["value"] > 0, by
            assert by[m]["ops"] == 8, by
            assert by[m]["host_cpus"] >= 1, by
        assert "writepath_speedup_vs_nopipe" in by

    def test_native_head_ab_smoke(self):
        """Native transport runs the matrix twice in the same run —
        head=native (C++ end-to-end serve) vs head=python (the
        TPU3FS_NATIVE_WRITE=0 serial lever) — and reports their ratio."""
        import pytest

        from benchmarks.write_bench import run as write_bench

        rows = write_bench(chunks=4, size=16 << 10, batch=4, rounds=1,
                           chains=2, replicas=2, transports=("native",))
        if any(r["metric"] == "writepath_error" for r in rows):
            pytest.skip("native toolchain unavailable")
        by = {(r["metric"], r.get("head")): r for r in rows if "value" in r}
        for head in ("native", "python"):
            for m in ("writepath_single", "writepath_batch"):
                assert by[(m, head)]["value"] > 0, by
        ab = by[("writepath_native_head_speedup", None)]
        assert ab["value"] > 0 and ab["host_cpus"] >= 1
        if ab["host_cpus"] == 1:
            assert "note" in ab  # core-bound caveat travels with the row


class TestSloBench:
    """benchmarks/slo_bench fast-mode smoke: both collector modes run
    over real sockets, samples actually reach the aggregator, and the
    detection-latency phase fires."""

    def test_small_run(self, tmp_path):
        from benchmarks.slo_bench import run as slo_bench

        res = slo_bench(chunks=8, size=32 << 10, batch=4, rounds=1,
                        out=str(tmp_path / "bs.json"))
        by = {r["metric"]: r for r in res["rows"]}
        assert by["slo_write_agg_off"]["value"] > 0
        assert by["slo_write_agg_slo_on"]["value"] > 0
        assert by["slo_agg_ingested"]["value"] > 0
        assert 0 < by["slo_detect_latency_ms"]["value"] < 5000


class TestNorthstarBench:
    """BASELINE.md headline workloads at test sizes: each phase must
    produce its e2e_* field and verify its own data integrity."""

    def test_graysort_shuffle(self):
        from benchmarks.northstar_bench import graysort_shuffle

        out = graysort_shuffle(total_mb=8, partitions=8, nodes=4, chains=8)
        assert out["e2e_graysort_shuffle_gibps"] > 0
        assert out["e2e_graysort_readback_gibps"] > 0
        assert out["graysort_bytes"] == 8 << 20
        assert out["graysort_placement_checked"]

    def test_kvcache_random_read_with_gc(self):
        from benchmarks.northstar_bench import kvcache_random_read

        out = kvcache_random_read(hot_entries=8, expired_entries=16,
                                  value_kb=16, reads=32, batch=8)
        assert out["e2e_kvcache_read_gibps"] > 0
        assert out["kvcache_gc_removed"] == 16  # exactly the expired pool
        assert out["e2e_kvcache_gc_remove_iops"] > 0

    def test_failed_target_rebuild(self):
        from benchmarks.northstar_bench import failed_target_rebuild

        out = failed_target_rebuild(file_mb=8, chunk_mb=1)
        assert out["e2e_rebuild_gibps"] > 0
        assert out["e2e_rebuild_bytes"] > 0


class TestTenantBench:
    """benchmarks/tenant_bench fast-mode smoke: the noisy-neighbor
    scenario scaled down — quota sheds fire, the class never sheds, and
    the victim keeps completing ops in every mode."""

    def test_small_run(self):
        from benchmarks.tenant_bench import run_bench
        from tpu3fs.tenant import registry

        out = run_bench(seconds=1.2, rounds=1, flooders=3,
                        queue_cap=16, engine="mem",
                        noisy_quota_bps=float(1 << 20))
        registry().clear()
        assert out["tenant_sheds"] > 0          # noisy excess shed
        assert out["fg_class_sheds"] == 0       # ...by ITS bucket only
        assert out["noisy_demand_ratio"] >= 4.0
        for mode, ops in out["victim_ops"].items():
            assert ops > 0, mode
        assert out["alone_p99_ms"] > 0 and out["on_p99_ms"] > 0
        # no latency acceptance at smoke scale (single tiny segment on a
        # loaded CI host); BENCH_TENANT.json carries the measured claim


class TestEcBench:
    """benchmarks/ec_bench fast-mode smoke: encode kernel, fused vs
    encode-then-write EC writes, delta-parity RMW, degraded reads, and
    the kill-a-target rebuild with recovery-read spread — over real
    sockets at test sizes."""

    def test_small_run(self):
        from benchmarks.ec_bench import run_bench

        rows = run_bench(k=3, m=1, stripes=6, size=1 << 16, fast=True)
        by = {r["metric"]: r for r in rows}
        assert by["ec_encode_host_3_1"]["value"] > 0
        ce = by["ec_chain_encode_2_2"]
        assert ce["value"] > 0 and ce["cr_equal_overhead_gibps"] > 0
        # multi-core rerun gate travels with the row, alongside the cores
        # the measurement actually had
        assert ce["host_cpus"] >= 1 and "acceptance" in ce
        # the offload IS the point: zero client encode CPU in chain mode
        assert ce["client_encode_cpu_s_per_gib"]["chain"] == 0.0
        assert ce["client_encode_cpu_s_per_gib"]["client"] > 0
        w = by["ec_write_fused_3_1"]
        assert w["value"] > 0 and w["baseline_encode_then_write"] > 0
        assert by["ec_substripe_rmw_3_1"]["value"] > 0
        d = by["ec_degraded_read_3_1"]
        assert d["value"] > 0 and d["clean_ms"] > 0
        r = by["ec_rebuild_3_1"]
        assert r["installed"] >= 6
        assert r["sources_spread_ok"]


class TestElasticBench:
    """benchmarks/elastic_bench fast-mode smoke: join-rebalance under a
    live fg load, drain-to-zero, byte verification — the measured claims
    live in BENCH_ELASTIC.json."""

    def test_small_run(self):
        from benchmarks.elastic_bench import run_bench

        row = run_bench(seconds=1.0, nodes=3, chains=2, replicas=2,
                        chunks=4, size=4096)
        assert row["moves"] >= 1 and row["drain_moves"] >= 1
        assert row["bytes_moved"] > 0
        assert row["verified_chunks"] == 8  # every oracle byte re-read
        assert row["steady_ops"] > 0 and row["rebalance_ops"] > 0
        assert row["drain_wall_s"] > 0
        # no latency acceptance at smoke scale; BENCH_ELASTIC.json
        # carries the measured fg-p99-under-rebalance claim


class TestScaleBench:
    """benchmarks/scale_bench smoke at toy N: the control-plane numbers
    in BENCH_SCALE.json come from the same functions at N=1000."""

    def test_size_and_ab_smoke(self):
        from benchmarks.scale_bench import bench_domain_ab, bench_size

        row = bench_size(20, 4)
        assert row["chains"] == 20
        assert row["heartbeat_fanin"]["round_s"] > 0
        assert row["routing_fanout"]["warm_bytes"] \
            < row["routing_fanout"]["cold_bytes"]
        assert row["domain_kill"]["chains_broken"] == 0
        ab = bench_domain_ab(n=12, domains=3)
        assert ab["aware"]["chains_broken"] == 0
        assert ab["aware"]["placement_violations"] == 0
        assert ab["blind"]["placement_violations"] > 0

    def test_rebalance_and_slo_smoke(self):
        from benchmarks.scale_bench import bench_slo_series

        row = bench_slo_series(16)
        assert row["rules_ok"] and row["ingest_s"] > 0


class TestBenchTrajectory:
    """tools/bench_trajectory renders every BENCH_*.json into
    docs/trajectory.md; the committed page must not go stale."""

    def test_render_all_artifacts(self):
        import glob as _glob
        import os as _os

        from tools.bench_trajectory import build

        root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        text = build(root)
        for p in _glob.glob(_os.path.join(root, "BENCH_*.json")):
            assert f"## {_os.path.basename(p)}" in text
        # BENCH_SOAK's partition trajectory renders as a multi-point series
        assert "partition_runs (3 points)" in text

    def test_committed_page_current(self):
        import os as _os

        from tools.bench_trajectory import build

        root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        with open(_os.path.join(root, "docs", "trajectory.md")) as f:
            committed = f.read()
        assert committed == build(root), (
            "docs/trajectory.md is stale — regenerate with "
            "python -m tools.bench_trajectory")
