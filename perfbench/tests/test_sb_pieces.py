"""The pieces the storage-bench cell brought: its plain reference against the
chip's own programs, and the driver whole on an in-process fabric of one
RS(3,1) table — correct as it stands, its injected faults retried, and the
control and each fault caught by the check named for it."""

import ast
import importlib
import inspect
import types

import numpy as np
import pytest

from perfbench.lib import reference as ref
from perfbench.lib import reference_sb as refsb
from perfbench.lib.proxies import SpanLog

CHUNK = 96 * 1024
SEED = 2147484001


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(inspect.getsource(refsb))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not any("tpu3fs" in n for n in names), names


def test_a_generation_is_the_seed_s_the_chunk_s_and_its_number():
    a = refsb.generation(SEED, 3, 2, 4096)
    assert a.dtype == np.uint8 and a.shape == (4096,)
    assert np.array_equal(a, refsb.generation(SEED, 3, 2, 4096))
    for other in ((SEED + 1, 3, 2), (SEED, 4, 2), (SEED, 3, 1),
                  (SEED + (1 << 32), 3, 2)):
        assert not np.array_equal(a, refsb.generation(*other, 4096))
    # each generation differs from the last in every word, by its step
    w1 = refsb.generation(SEED, 3, 1, 4096).view("<u4")
    w2 = a.view("<u4")
    assert ((w2 - w1) == np.uint32(refsb.step(SEED, 3, 2))).all()
    assert refsb.step(SEED, 3, 2) % 2 == 1


def test_the_chip_s_programs_make_the_reference_s_generations():
    from perfbench.drivers.storage_bench import _programs

    first, following = _programs(4096 // 4)
    chunks = [0, 5, 31]
    rows = first(np.array([refsb.chunk_key(SEED, c) for c in chunks],
                          dtype=np.uint32))
    for gen in range(3):
        for c, row in zip(chunks, rows):
            got = np.asarray(row).astype("<u4").view(np.uint8)
            assert np.array_equal(got, refsb.generation(SEED, c, gen, 4096))
        rows = following(rows, np.array(
            [refsb.step(SEED, c, gen + 1) for c in chunks], dtype=np.uint32))


def test_the_reference_fingerprints_each_generation_s_bytes():
    chunks = {5: [0, 2, 7], 9: [1]}
    for c, gens in chunks.items():
        got = refsb.fingerprints(SEED, c, gens, 4096)
        assert set(got) == set(gens)
        for g in gens:
            assert got[g] == ref.fingerprint_np(
                refsb.generation(SEED, c, g, 4096))
    assert got[1] != refsb.fingerprints(SEED, 9, [2], 4096)[2]


def test_the_chip_s_row_fingerprints_are_the_reference_s():
    import jax

    from perfbench.drivers.storage_bench import _prints

    gens = [(3, 0), (3, 4), (11, 1)]
    rows = np.stack([refsb.generation(SEED, c, g, 4096) for c, g in gens])
    got = np.asarray(_prints()(jax.device_put(rows)))
    assert got.shape == (3, 2) and got.dtype == np.uint32
    for (c, g), fp in zip(gens, got):
        assert (int(fp[0]), int(fp[1])) == refsb.fingerprints(
            SEED, c, [g], 4096)[g]


def test_stored_shards_are_the_independent_encode_with_crcs():
    data = refsb.generation(SEED, 1, 0, CHUNK).tobytes()
    shards = refsb.stored_shards(data, CHUNK, 3, 1)
    assert [len(s) for s, _ in shards] == [CHUNK // 3] * 4
    parity = np.bitwise_xor.reduce(
        [np.frombuffer(s, np.uint8) for s, _ in shards[:3]])
    assert shards[3][0] == parity.tobytes()   # RS(3,1)'s parity row: XOR
    assert all(crc == ref.crc32c(s) for s, crc in shards)


PARAMS = {"workers": 2, "batch": 2, "read_share": 0.5, "round_ops": 4,
          "error_prob": 0.2,
          "fault_settle_s": 0, "keep_every": 2, "verify_read_batches": 4,
          "chunk_size": CHUNK, "chunks": 8}
CONFIG = {"chunk_size": 4 << 20, "chunks": 512,
          "cluster": {"tables": [{"chains": [
              {"chain_id": c, "targets": 4, "ec_k": 3, "ec_m": 1}
              for c in (1, 2)]}]}}


class _Admin:
    """mgmtd's config push, in one process: the storage type's [faults]
    section arms the process's own fault plane."""

    def __init__(self, fab):
        self.fab = fab
        self.content = ""

    def get_config(self, node_type):
        return types.SimpleNamespace(content=self.content)

    def set_config(self, node_type, content):
        import tomllib

        from tpu3fs.utils.fault_injection import plane

        self.content = content
        faults = tomllib.loads(content)["faults"]
        plane().configure(faults["spec"], int(faults["seed"]))
        return 1

    def refresh_routing(self):
        return self.fab.routing()


def run_driver(monkeypatch, tmp_path, fault: str = "", ops: int = 8):
    """The cell's driver, whole, on an in-process fabric (two RS(3,1)
    chains over four nodes, native engine, 96-KiB chunks): set-up, warm-up
    (the fault rules go out), `ops` batches a worker, the window's end, the
    comparison. -> ({check: value}, ctx)."""
    import jax

    from tpu3fs.client.storage_client import RetryOptions, StorageClient
    from tpu3fs.fabric import Fabric, SystemSetupConfig
    from tpu3fs.ops.crc32c import CrcVerifier
    from tpu3fs.ops.stripe import StripeCodec
    from tpu3fs.utils.fault_injection import plane

    from perfbench.drivers import storage_bench

    # whatever a fault patches is put back when the test ends
    for cls, name in ((StripeCodec, "encode_batch"), (CrcVerifier, "check"),
                      (StorageClient, "write_stripes")):
        monkeypatch.setattr(cls, name, getattr(cls, name))
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=4, num_chains=2, ec_k=3, ec_m=1, chunk_size=CHUNK,
        engine="native", engine_dir=str(tmp_path)))
    routing = fab.routing()
    ctx = types.SimpleNamespace(
        seed=SEED, params=dict(PARAMS),
        config={**CONFIG, "cluster": {"tables": [{"chains": [
            {"chain_id": c, "targets": 4, "ec_k": 3, "ec_m": 1}
            for c in sorted(routing.chains)]}]}},
        rehearse=True, trace=False, jax=jax, chip=jax.devices()[0],
        view=fab, new_view=lambda tag: fab,
        retry=RetryOptions(backoff_base_s=0.001, backoff_max_s=0.01),
        wrap=lambda obj, layer: obj, say=lambda *a: None, spans=SpanLog(),
        requests=[], counters={}, run_dir=str(tmp_path), after_window=[],
        cluster=types.SimpleNamespace(admin=_Admin(fab)))
    if fault:
        importlib.import_module(f"perfbench.faults.{fault}").plant(ctx)
    driver = storage_bench.Driver(ctx)
    try:
        driver.setup()
        driver.warm()
        for w, client in enumerate(driver.clients):
            for op in range(ops):
                driver.run(client, w, op)
        ctx.counters["injected_retried"] = driver.injected() - driver.before
        for hook in ctx.after_window:
            hook(ctx, driver)
        checks = {c.name: c.value for c in driver.verify()}
    finally:
        plane().clear()
        driver.close()
        fab.close()
    return checks, ctx


def test_the_storage_bench_comparison_is_clean_on_a_sound_program(
        monkeypatch, tmp_path):
    checks, ctx = run_driver(monkeypatch, tmp_path)
    assert checks == {"reads_of_unacked_version": 0, "read_bytes_wrong": 0,
                      "read_checksums_wrong": 0,
                      "read_fingerprints_wrong": 0, "verify_blind": 0,
                      "stored_shards_wrong": 0, "codecs_on_host": 0,
                      "injected_faults_absent": 0}
    assert all(r["ok"] for r in ctx.requests) and len(ctx.requests) == 16
    # rounds of four ops a worker, two reads and two writes each
    kinds = [r["kind"] for r in ctx.requests]
    assert kinds.count("read") == kinds.count("write") == 8
    assert all(r["load_bytes"] + r["store_bytes"] == 2 * CHUNK
               for r in ctx.requests)
    # the faults fired and every op they met was retried to success
    assert ctx.counters["injected_retried"] > 0


# reads a window decoded around a refused or rewritten shard: with the
# control's parity of zeros, wrong bytes that carry their own checksum
DECODED_READ_CHECKS = {"read_fingerprints_wrong", "read_bytes_wrong",
                       "read_checksums_wrong"}


@pytest.mark.parametrize("fault,bitten", [
    ("sb_parity_zeroed", {"stored_shards_wrong"}),
    ("sb_shard_altered", {"stored_shards_wrong"}),
    ("sb_verify_skipped", {"verify_blind"}),
])
def test_each_storage_bench_fault_bites_its_check(monkeypatch, tmp_path,
                                                  fault, bitten):
    checks, ctx = run_driver(monkeypatch, tmp_path, fault)
    got = {name for name, value in checks.items() if value > 0}
    if fault == "sb_parity_zeroed":
        got -= DECODED_READ_CHECKS
    assert got == bitten
    assert all(r["ok"] for r in ctx.requests)
    if fault == "sb_shard_altered":
        assert checks["stored_shards_wrong"] == 1
    if fault == "sb_parity_zeroed":
        assert checks["stored_shards_wrong"] == 8   # every stripe's parity


def test_a_read_with_wrong_bytes_and_their_own_checksum_is_caught(
        monkeypatch, tmp_path):
    """What the device verify cannot see — bytes that carry the checksum
    of what they are, as a shard rebuilt from bad survivors does — the
    fingerprint of every read does: one window read is altered so."""
    from tpu3fs.client.storage_client import StorageClient
    from tpu3fs.ops.crc32c import crc32c
    from tpu3fs.storage.types import Checksum

    inner = StorageClient.batch_read
    calls = []

    def batch_read(self, reqs, *, with_checksum=False):
        out = inner(self, reqs, with_checksum=with_checksum)
        calls.append(len(calls))
        if len(calls) == 4:   # past the warm-up's read a worker
            bad = bytearray(out[0].data)
            bad[77] ^= 0x10
            out[0].data = bytes(bad)
            out[0].checksum = Checksum(crc32c(out[0].data), len(bad))
        return out

    monkeypatch.setattr(StorageClient, "batch_read", batch_read)
    checks, ctx = run_driver(monkeypatch, tmp_path)
    assert checks["read_fingerprints_wrong"] == 1
    assert checks["stored_shards_wrong"] == checks["verify_blind"] == 0
    assert all(r["ok"] for r in ctx.requests)


def test_a_program_without_the_read_checksum_cannot_run_the_deployment(
        monkeypatch):
    from tpu3fs.client.storage_client import StorageClient

    from perfbench.drivers import storage_bench

    monkeypatch.setattr(StorageClient, "batch_read",
                        lambda self, reqs: [])
    ctx = types.SimpleNamespace(config=CONFIG, params=dict(PARAMS))
    with pytest.raises(SystemExit) as ei:
        storage_bench.Driver(ctx)
    assert "cannot run this deployment" in str(ei.value)
