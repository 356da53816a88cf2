"""The pieces the small-I/O cell brought: the plain reference of its blocks,
the seed's draw of offsets, and the driver's comparison on a tiny in-process
fabric — correct as it stands, and each fault caught by the check named for
it."""

import ast
import importlib
import inspect
import types

import numpy as np
import pytest

from perfbench.lib import reference as ref
from perfbench.lib import reference_blocks as refb
from perfbench.lib.proxies import SpanLog

BS = 4096


def test_the_block_reference_imports_nothing_of_the_program():
    tree = ast.parse(inspect.getsource(refb))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not any("tpu3fs" in n for n in names), names


def test_a_file_is_the_seed_s_and_the_job_s():
    a = refb.file_bytes(2147484001, 0, 4 * BS)
    assert a.dtype == np.uint8 and a.shape == (4 * BS,)
    assert (a == refb.file_bytes(2147484001, 0, 4 * BS)).all()
    assert (a != refb.file_bytes(2147484001, 1, 4 * BS)).any()
    assert (a != refb.file_bytes(2147484002, 0, 4 * BS)).any()


def test_fingerprints_are_the_words_sum_and_golden_maximum():
    data = refb.file_bytes(3, 2, 8 * BS)
    sums, maxes = refb.fingerprints(data, BS)
    assert sums.dtype == np.uint32 and sums.shape == (8,)
    for blk in (0, 5, 7):
        raw = data[blk * BS:(blk + 1) * BS].tobytes()
        words = [int.from_bytes(raw[i:i + 4], "little")
                 for i in range(0, BS, 4)]
        assert int(sums[blk]) == sum(words) % (1 << 32)
        assert int(maxes[blk]) == max(w * ref.GOLDEN % (1 << 32)
                                      for w in words)
    rows = refb.blocks_of(data, [5, 0, 5], BS)
    assert rows.shape == (3, BS)
    assert rows[0].tobytes() == data[5 * BS:6 * BS].tobytes()
    assert (rows[0] == rows[2]).all()


def test_the_offsets_draw_is_the_seed_s():
    a = refb.block_draws(2147484001, 1, 65536, 1024)
    b = refb.block_draws(2147484001, 1, 65536, 1024)
    first, second = next(a), next(a)
    assert first.shape == (1024,) and (first != second).any()
    assert (first == next(b)).all() and (second == next(b)).all()
    assert 0 <= first.min() and first.max() < 65536
    assert (first != next(refb.block_draws(2147484001, 2, 65536, 1024))).any()
    assert (first != next(refb.block_draws(7, 1, 65536, 1024))).any()
    # with replacement, uniform: about 1024 - 8 distinct of 65536
    assert 1000 < len(set(first.tolist())) <= 1024


CONFIG = {
    "io": {"bs": BS, "iodepth": 32, "ior_depth": 32,
           "file_bytes": 64 * BS},
    "cluster": {"tables": [{"chains": [{"targets": 3}]}]},
}
PARAMS = {"jobs": 2, "wait_timeout_s": 20, "verify_batches": 4,
          "verify_chunks": 2}


def run_driver(monkeypatch, fault: str = "", batches: int = 3):
    """The cell's driver, whole, on an in-process fabric (CR-3 over four
    nodes, 64-KiB chunks): set-up, warm-up, `batches` batches a job, the
    comparison. -> ({check: value}, ctx)."""
    import jax

    from tpu3fs.client.file_io import FileIoClient
    from tpu3fs.client.storage_client import RetryOptions
    from tpu3fs.fabric import Fabric, SystemSetupConfig

    from perfbench.drivers import uring_batches

    # whatever a fault patches is put back when the test ends
    monkeypatch.setattr(FileIoClient, "batch_read_into",
                        FileIoClient.batch_read_into)
    fab = Fabric(SystemSetupConfig(num_storage_nodes=4, num_chains=4,
                                   num_replicas=3, chunk_size=16 * BS))
    ctx = types.SimpleNamespace(
        seed=2147484001, params=dict(PARAMS), config=CONFIG, rehearse=True,
        jax=jax, chip=jax.devices()[0], view=fab, new_view=lambda tag: fab,
        retry=RetryOptions(), wrap=lambda obj, layer: obj,
        say=lambda *a: None, spans=SpanLog(), requests=[], counters={},
        cluster=types.SimpleNamespace(admin=types.SimpleNamespace(
            refresh_routing=fab.routing)))
    if fault:
        importlib.import_module(f"perfbench.faults.{fault}").plant(ctx)
    driver = uring_batches.Driver(ctx)
    try:
        driver.setup()
        driver.warm()
        for job in driver.jobs:
            for b in range(batches):
                ctx.requests.append(driver.run_batch(
                    job, b, next(job.draws), keep=b == 1))
        for key in ("batches", "sqes", "short_drains"):
            ctx.counters[f"uring_{key}"] = (driver.agent.totals[key]
                                            - driver.before[key])
        checks = {c.name: c.value for c in driver.verify()}
    finally:
        driver.close()
        fab.close()
    return checks, ctx


def test_the_driver_s_comparison_is_clean_on_a_sound_program(monkeypatch):
    checks, ctx = run_driver(monkeypatch)
    assert checks == {"cqe_errors": 0, "cqes_lost_or_doubled": 0,
                      "rows_wrong_in_hbm": 0, "blocks_wrong_bytes": 0,
                      "replicas_wrong": 0, "short_drains": 0, "shm_left": 0}
    assert all(r["ok"] for r in ctx.requests) and len(ctx.requests) == 6
    assert all(r["load_bytes"] == 32 * BS for r in ctx.requests)
    assert set(ctx.requests[0]["phases"]) == {"prep", "wait", "land"}
    # every window byte crossed a file-mode SQE: one drain of 32 a batch
    assert ctx.counters == {"uring_batches": 6, "uring_sqes": 6 * 32,
                            "uring_short_drains": 0}


@pytest.mark.parametrize("fault,bitten", [
    ("uring_cqe_without_read", {"rows_wrong_in_hbm", "blocks_wrong_bytes"}),
    ("uring_block_altered", {"rows_wrong_in_hbm", "blocks_wrong_bytes"}),
    ("uring_wrong_offset", {"rows_wrong_in_hbm", "blocks_wrong_bytes"}),
])
def test_each_fault_bites_the_check_named_for_it(monkeypatch, fault, bitten):
    checks, ctx = run_driver(monkeypatch, fault)
    assert {name for name, value in checks.items() if value > 0} == bitten
    # the answers LOOKED right: every CQE said 4096, none lost
    assert all(r["ok"] for r in ctx.requests)
    if fault == "uring_cqe_without_read":
        # every second row of a batch (the slot kept the batch before it)
        assert checks["rows_wrong_in_hbm"] >= 6 * 16 - 6
    if fault == "uring_block_altered":
        assert checks["rows_wrong_in_hbm"] == 6      # one row a batch


def test_a_program_without_the_batch_cannot_run_the_deployment(monkeypatch):
    from tpu3fs.client.file_io import FileIoClient

    from perfbench.drivers import uring_batches

    monkeypatch.delattr(FileIoClient, "batch_read_into")
    ctx = types.SimpleNamespace(config=CONFIG, params=dict(PARAMS))
    with pytest.raises(SystemExit) as ei:
        uring_batches.Driver(ctx)
    assert "cannot run this deployment" in str(ei.value)
