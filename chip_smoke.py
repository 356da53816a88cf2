#!/usr/bin/env python3
"""chip_smoke.py: drive tpu3fs's served path once, on one TPU chip.

    python3 chip_smoke.py            # the whole run; needs a TPU

What a bare run does, in order:

  kernels   the RS Pallas kernel (non-interpreted), the XOR rebuild and
            BatchCrc32c compile at every (k, m, S) of KERNEL_SHAPES and
            agree bit for bit with the numpy gold and the scalar CRC
  cluster   mgmtd + 4 storage (native engine, data on disk) + meta boot as
            real processes through tpu3fs.bin.*; this process is the client
            and the ONE owner of the chip, and checks that itself
  cr3       >= 1 GiB written and read back through FileIoClient on CR-3
            with 1 MiB chunks, random 4 KiB reads, client/inmem.py as the
            reference for overwrite and short-read semantics
  ec        >= 1 GiB written and read back on RS(12,4) over 16 targets
            with encode and CRC on the chip; SIGKILL of one storage process
            (4 of 16 shards), degraded reads decoded on the chip; restart
            with an empty disk, resync to SERVING, clean reads
  kvcache   >= 1 GiB of 128 KiB blocks through PrefixBlockStore into HBM
  ckpt      a >= 1 GiB pytree that lives on the chip saved and restored
  dataload  32 KiB records streamed by DataLoader into a jitted step
  fourchip  the mesh kernels on real devices; NOT RUN with fewer than four

Any failure is a non-zero exit and no result line. Without a TPU the run
stops at once ("no TPU"). The last line of a whole, passing run is one JSON
object with exactly these keys:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
The per-section outcome and the seed are on the lines before it.

Rehearsal and bring-up options are explicit and never the default:
--rehearse-cpu runs everything tiny on the CPU backend to check the script
itself, says so, prints no result line; --sections and --size-mib cut a
chip run down, say so, and print no result line either.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))

LEGS = ("cr3", "ec", "kvcache", "ckpt", "dataload")
SECTIONS = ("kernels",) + LEGS + ("fourchip",)
NEED_CLUSTER = LEGS + ("fourchip",)

MIB = 1 << 20
CHUNK = 1 * MIB                  # upstream kChunkSize (fbs/storage/Common.h)
EC_K, EC_M = 12, 4
STORAGE_NODES = (101, 102, 103, 104)
VICTIM = 102                     # holds EC shards 1, 5, 9 (data) and 13
CR_CHAINS = (901, 902, 903, 904)  # CR-3: chain c skips node c
EC_CHAIN = 950
KV_BLOCK_SHAPE = (2, 16, 16, 128)  # float16 K/V page: 128 KiB
KV_BLOCK_TOKENS = 16
RECORD_TOKENS = 8192             # int32 tokens: 32 KiB records

# (k, m, shard bytes, where the shape comes from)
KERNEL_SHAPES = (
    (12, 4, 87552, "RS(12,4) 1 MiB chunks: this run's EC layout, BASELINE 4"),
    (12, 4, 11264, "RS(12,4) 128 KiB values: BASELINE 3"),
    (8, 2, 131072, "RS(8,2) 1 MiB chunks: BASELINE 2"),
    (3, 1, 1398272, "RS(3,1) 4 MiB chunks: BASELINE 1, gf2_matmul pad path"),
    (2, 1, 524288, "RS(2,1) 1 MiB chunks"),
    (2, 1, 2048, "RS(2,1) 4 KiB chunks"),
)
REHEARSAL_KERNEL_SHAPES = (
    (12, 4, 1024, "rehearsal"), (3, 1, 1536, "rehearsal"),
    (2, 1, 64, "rehearsal"),
)


def say(msg: str) -> None:
    print(msg, flush=True)


def child_env() -> dict:
    """Environment of every service child: pinned to the CPU backend, and
    without the switch that sends a process's stripe codec to the device.
    The chip has one owner, this process; StripeCodec reads the switch per
    process, so an inherited =1 would make four storage processes reach for
    the one chip on their first stripe."""
    env = {k: v for k, v in os.environ.items()
           if k != "TPU3FS_STRIPE_DEVICE"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return env


def result_line(device: dict) -> str:
    """The last line of a whole, passing chip run: exactly these keys, the
    device as JAX reports it. Sections, seed and timings are printed on
    the lines before it, never inside it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --------------------------------------------------------------------------
# device, compile accounting
# --------------------------------------------------------------------------

class CompileMeter:
    """JAX's own compile events: programs compiled, seconds spent, and how
    many came out of the persistent cache — so a second run's hits show."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.slow: list = []   # (seconds, program) of every compile >= 5s
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration
            if duration >= 5.0:
                self.slow.append((duration, kw.get("fun_name", "?")))

    def snapshot(self):
        return (self.programs, self.seconds, self.hits, self.misses,
                len(self.slow))

    def since(self, snap) -> str:
        p, s, h, m, n_slow = (a - b for a, b in zip(self.snapshot(), snap))
        slow = "".join(f"; {name} {dt:.0f}s"
                       for dt, name in self.slow[len(self.slow) - n_slow:])
        return (f"compile/set-up: {p} programs in {s:.1f}s "
                f"(persistent cache: {h} hits, {m} misses{slow})")


def require_device(rehearse: bool):
    """Assert the accelerator, print what it is, turn the compile cache on.
    -> (jax, device dict, CompileMeter)."""
    from tpu3fs.utils.compile_cache import enable_compile_cache

    import jax
    import jaxlib

    cache = enable_compile_cache()
    meter = CompileMeter()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']}  jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"python={sys.version.split()[0]}")
    if rehearse:
        if dev.platform != "cpu":
            sys.exit("chip_smoke: --rehearse-cpu wants JAX_PLATFORMS=cpu")
    elif dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax's default backend is "
                 f"{dev.platform!r}); nothing ran, no result")
    n_cached = len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0
    say(f"compile cache: {cache or 'off (process pinned to the cpu)'}"
        f" — {n_cached} entries at start")
    return jax, device, meter


def build_native() -> None:
    """native/*.so is git-ignored: build both libraries from the committed
    sources ONCE, before any child starts (six processes booting on a tree
    with no .so would race on one output file), and prove they load."""
    t0 = time.time()
    subprocess.run(["make", "-B", "-j4", "-C", os.path.join(HERE, "native")],
                   check=True, stdout=subprocess.DEVNULL)
    from tpu3fs.ops import native_ec
    from tpu3fs.ops.crc32c import _native_crc
    from tpu3fs.rpc import native_net

    assert native_ec.available(), "libtpu3fs_engine.so: EC entry points"
    assert _native_crc() is not None, "libtpu3fs_engine.so: crc32c"
    assert native_net._load_lib() is not None, "libtpu3fs_rpc.so"
    say(f"native: built and loaded libtpu3fs_engine.so + libtpu3fs_rpc.so "
        f"in {time.time() - t0:.1f}s")


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def section_kernels(ctx) -> str:
    import jax.numpy as jnp
    import numpy as np

    from tpu3fs.ops import pallas_rs
    from tpu3fs.ops.crc32c import crc32c
    from tpu3fs.ops.rs import RSCode
    from tpu3fs.ops.stripe import get_codec

    on_chip = not ctx.rehearse
    if on_chip:
        assert pallas_rs.backend_supports_pallas(), "Pallas not selected"
    shapes = REHEARSAL_KERNEL_SHAPES if ctx.rehearse else KERNEL_SHAPES
    batch = 3  # not a power of two: the codec's bucket padding runs too
    checks = 0
    for k, m, S, why in shapes:
        t_shape = time.time()
        rng = np.random.default_rng([ctx.seed, k, m, S])
        data = rng.integers(0, 256, (batch, k, S), dtype=np.uint8)
        rs = RSCode(k, m)
        parity = rs.encode_np(data)
        shards = np.concatenate([data, parity], axis=1)
        rows = shards.reshape(batch * (k + m), S)
        crcs = np.array([crc32c(r.tobytes()) for r in rows], dtype=np.uint32)

        got = np.asarray(rs.encode(jnp.asarray(data)))
        assert np.array_equal(got, parity), f"encode RS({k},{m}) S={S}"
        if on_chip:
            assert "encode" in rs._pallas_matrices, "encode skipped Pallas"
        cases = [("xor-1-loss", tuple(i for i in range(k + 1) if i != 1),
                  (1,))]
        if m >= 2:
            cases.append(("m-loss", tuple(range(m, k + m)),
                          tuple(range(m))))
            # one loss WITHOUT parity row 0 among the survivors: a 1-row
            # decode matrix through the bit-matmul, not the XOR shortcut
            cases.append(("1-loss-gf", tuple(range(1, k)) + (k + 1,), (0,)))
        if (k, m) == (EC_K, EC_M):
            # what the degraded leg decodes: the victim's three data shards
            lost = (1, 5, 9)
            present = tuple(j for j in range(k + m)
                            if j % 4 != 1)[:k]
            cases.append(("victim-3-loss", present, lost))
        for name, present, lost in cases:
            out = np.asarray(rs.reconstruct(
                present, lost, jnp.asarray(shards[:, list(present)])))
            assert np.array_equal(out, shards[:, list(lost)]), \
                f"{name} RS({k},{m}) S={S}"
            checks += 1

        codec = get_codec(k, m, S)
        assert not codec._use_host(), "codec fell to the host"
        # the served entry points: the fused encode+CRC program, decode,
        # and BatchCrc32c alone (crc_batch is its jitted compute)
        s_dev, c_dev = codec.encode_batch(data)
        assert np.array_equal(s_dev, shards), f"encode_batch S={S}"
        assert np.array_equal(c_dev.reshape(-1), crcs), f"encode_batch crc"
        name, present, lost = cases[-1]
        r_dev = codec.reconstruct_batch(present, lost,
                                        shards[:, list(present)])
        assert np.array_equal(r_dev, shards[:, list(lost)])
        assert np.array_equal(codec.crc_batch(rows), crcs), \
            f"BatchCrc32c S={S}"
        checks += 5
        say(f"  RS({k},{m}) S={S:>7}: encode, {len(cases)} decodes, CRC, "
            f"codec bit-exact  {time.time() - t_shape:5.1f}s  [{why}]")
    return f"{len(shapes)} shapes, {checks} bit-exact comparisons"


# --------------------------------------------------------------------------
# cluster
# --------------------------------------------------------------------------

class Cluster:
    def __init__(self, run_dir: str):
        self.run_dir = run_dir
        self.logs = os.path.join(run_dir, "logs")
        os.makedirs(self.logs, exist_ok=True)
        self.env = child_env()
        self.procs: dict = {}
        self.mport = free_port()
        self.admin = None

    # -- processes ----------------------------------------------------------
    # `python -m module` preceded by PR_SET_PDEATHSIG(SIGKILL): the kernel
    # kills the child if this process dies first, so even a SIGKILL of the
    # smoke leaves nothing running. Set by the child itself because a
    # preexec_fn would fork() a process full of JAX threads.
    _LAUNCH = ("import ctypes, runpy, signal, sys; "
               "ctypes.CDLL(None).prctl(1, signal.SIGKILL); "
               "sys.argv = sys.argv[1:]; "
               "runpy.run_module(sys.argv[0], run_name='__main__', "
               "alter_sys=True)")

    def spawn(self, name: str, module: str, *args: str) -> None:
        with open(os.path.join(self.logs, f"{name}.log"), "ab") as log:
            self.procs[name] = subprocess.Popen(
                [sys.executable, "-c", self._LAUNCH, module, *args],
                env=self.env, cwd=self.run_dir, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def data_dir(self, nid: int) -> str:
        return os.path.join(self.run_dir, f"storage_{nid}")

    def spawn_storage(self, nid: int) -> None:
        self.spawn(
            f"storage{nid}", "tpu3fs.bin.storage_main",
            "--node-id", str(nid), "--mgmtd", f"127.0.0.1:{self.mport}",
            "--heartbeat_interval", "0.3", "--config.engine=native",
            f"--config.data_dir={self.data_dir(nid)}",
            "--config.target_scan_interval_s=0.3",
            "--config.resync_interval_s=0.3")

    def stop(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def log_tails(self, lines: int = 12) -> str:
        out = []
        for name in sorted(os.listdir(self.logs)):
            with open(os.path.join(self.logs, name), "rb") as f:
                tail = f.read()[-4000:].decode("utf-8", "replace")
            kept = [ln for ln in tail.splitlines()  # jax's import-time note
                    if "hugepages" not in ln and "warnings.warn" not in ln]
            out.append(f"--- {name} ---\n" + "\n".join(kept[-lines:]))
        return "\n".join(out)

    # -- boot ---------------------------------------------------------------
    def wait_routing(self, pred, what: str, budget: float = 60.0):
        deadline = time.time() + budget
        while time.time() < deadline:
            routing = self.admin.refresh_routing()
            if pred(routing):
                return routing
            time.sleep(0.3)
        raise AssertionError(f"timeout ({budget:.0f}s) waiting for {what}")

    def boot(self) -> str:
        from tpu3fs.mgmtd.types import LocalTargetState, NodeType
        from tpu3fs.rpc.services import MgmtdAdminRpcClient

        self.spawn("mgmtd", "tpu3fs.bin.mgmtd_main", "--node-id", "1",
                   "--port", str(self.mport),
                   "--config.tick_interval_s=0.3",
                   "--config.heartbeat_timeout_s=4.0")
        deadline = time.time() + 90
        while True:
            try:
                socket.create_connection(("127.0.0.1", self.mport),
                                         timeout=0.5).close()
                break
            except OSError:
                assert time.time() < deadline, "mgmtd never listened"
                assert self.procs["mgmtd"].poll() is None, "mgmtd died"
                time.sleep(0.3)
        for nid in STORAGE_NODES:
            self.spawn_storage(nid)
        self.admin = MgmtdAdminRpcClient(("127.0.0.1", self.mport))
        admin = self.admin
        tid = 1
        # CR-3 (three replicas per the design notes): chain c on the three
        # nodes other than node c -> 12 targets, 3 per storage process
        for c, chain_id in enumerate(CR_CHAINS):
            tids = []
            for r in range(1, 4):
                admin.create_target(
                    tid, node_id=STORAGE_NODES[(c + r) % 4])
                tids.append(tid)
                tid += 1
            admin.upload_chain(chain_id, tids)
        admin.upload_chain_table(1, list(CR_CHAINS))
        # RS(12,4): ONE chain of 16 shard targets, shard j on node j % 4
        # -> 4 per storage process, so a dead process is 4 lost shards
        tids = []
        for j in range(EC_K + EC_M):
            admin.create_target(tid, node_id=STORAGE_NODES[j % 4])
            tids.append(tid)
            tid += 1
        admin.upload_chain(EC_CHAIN, tids, ec_k=EC_K, ec_m=EC_M)
        admin.upload_chain_table(2, [EC_CHAIN])
        n_targets = tid - 1
        self.wait_routing(
            lambda r: len(r.targets) == n_targets and all(
                t.local_state == LocalTargetState.UPTODATE
                for t in r.targets.values()),
            f"{n_targets} targets UPTODATE", budget=120)
        # files stripe over all four CR-3 chains of table 1
        self.spawn("meta", "tpu3fs.bin.meta_main", "--node-id", "201",
                   "--mgmtd", f"127.0.0.1:{self.mport}",
                   "--heartbeat_interval", "0.3", "--config.stripe=4")
        self.wait_routing(
            lambda r: any(n.type == NodeType.META and n.host
                          for n in r.nodes.values()),
            "meta server registered", budget=90)
        return (f"mgmtd + {len(STORAGE_NODES)} storage (native engine) + "
                f"meta; {len(CR_CHAINS)} CR-3 chains + one RS({EC_K},{EC_M})"
                f" chain, {n_targets} targets")

    # -- who holds the chip -------------------------------------------------
    @staticmethod
    def _chip_marks(pid: int) -> list:
        """Evidence that a process initialised a non-CPU backend: libtpu.so
        mapped (jax maps it only when it brings the TPU client up), or an
        accelerator device file open."""
        marks = []
        with open(f"/proc/{pid}/maps") as f:
            if any("libtpu.so" in line for line in f):
                marks.append("libtpu.so mapped")
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio")):
                marks.append(f"{target} open")
        return marks

    def verify_one_owner(self, rehearse: bool) -> str:
        mine = self._chip_marks(os.getpid())
        if not rehearse:
            assert mine, ("this process holds the chip yet shows no mark "
                          "of it in /proc: the ownership check is blind")
        for name, p in self.procs.items():
            if p.poll() is not None:
                continue  # the SIGKILLed victim, already reaped
            with open(f"/proc/{p.pid}/environ", "rb") as f:
                env = dict(kv.split(b"=", 1) for kv in f.read().split(b"\0")
                           if b"=" in kv)
            assert env.get(b"JAX_PLATFORMS") == b"cpu", (name, "not pinned")
            assert b"TPU3FS_STRIPE_DEVICE" not in env, (name, "has switch")
            marks = self._chip_marks(p.pid)
            assert not marks, f"{name} (pid {p.pid}) touched the chip: {marks}"
        alive = sum(p.poll() is None for p in self.procs.values())
        return (f"{alive} children pinned to the cpu, none touched the "
                f"chip; this process: {', '.join(mine) or 'cpu rehearsal'}")


# --------------------------------------------------------------------------
# file legs
# --------------------------------------------------------------------------

class Files:
    """N files of seed-generated bytes behind one FileIoClient, mirrored
    for one of them into the in-memory reference client."""

    def __init__(self, ctx, tag: str, root: str, layout=None):
        import numpy as np

        from tpu3fs.client.file_io import FileIoClient
        from tpu3fs.client.inmem import StorageClientInMem

        self.ctx, self.tag, self.root, self.layout = ctx, tag, root, layout
        self.fio = ctx.view.file_client(retry=ctx.retry)
        self.ref = FileIoClient(StorageClientInMem())
        self.rng = np.random.default_rng(
            [ctx.seed, sum(map(ord, tag))])
        n_files = 4
        per = max(CHUNK, ctx.leg_bytes // n_files // CHUNK * CHUNK)
        self.blobs = [self.rng.bytes(per) for _ in range(n_files)]
        self.paths = [f"{root}/f{i}" for i in range(n_files)]
        self.inodes: list = []

    @property
    def nbytes(self) -> int:
        return sum(len(b) for b in self.blobs)

    def write_all(self) -> float:
        from tpu3fs.meta.store import OpenFlags

        meta = self.ctx.view.meta
        meta.mkdirs(self.root, recursive=True)
        extra = {} if self.layout is None else {"layout": self.layout}
        t0 = time.time()
        for path, blob in zip(self.paths, self.blobs):
            rsp = meta.create(path, flags=OpenFlags.WRITE | OpenFlags.CREATE,
                              **extra)
            assert self.fio.write(rsp.inode, 0, blob) == len(blob)
            meta.close(rsp.inode.id, rsp.session_id, length_hint=len(blob),
                       wrote=True)
            inode = meta.stat(path)
            assert inode.length == len(blob), (path, inode.length)
            self.inodes.append(inode)
        dt = time.time() - t0
        self.ref.write(self.inodes[0], 0, self.blobs[0])
        return dt

    def read_all(self, what: str) -> float:
        t0 = time.time()
        piece = 64 * MIB
        for inode, blob in zip(self.inodes, self.blobs):
            for off in range(0, len(blob), piece):
                got = self.fio.read(inode, off, piece)
                assert got == blob[off:off + piece], \
                    f"{self.tag} {what}: file {inode.id} differs at {off}"
        return time.time() - t0

    def random_reads(self, n: int, size: int = 4096) -> None:
        for _ in range(n):
            i = int(self.rng.integers(len(self.blobs)))
            off = int(self.rng.integers(0, len(self.blobs[i]) - size))
            got = self.fio.read(self.inodes[i], off, size)
            assert got == self.blobs[i][off:off + size], (self.tag, i, off)

    def semantics_vs_inmem(self, n: int) -> None:
        """The same overwrites and reads against the cluster and against
        StorageClientInMem: unaligned overwrites across chunk borders, then
        reads, the last ones running off the end of the file."""
        inode, size = self.inodes[0], len(self.blobs[0])
        blob = bytearray(self.blobs[0])
        for _ in range(n):
            ln = int(self.rng.integers(1, 200_000))
            off = int(self.rng.integers(0, size - ln))
            patch = self.rng.bytes(ln)
            assert self.fio.write(inode, off, patch) == ln
            assert self.ref.write(inode, off, patch) == ln
            blob[off:off + ln] = patch
        self.blobs[0] = bytes(blob)
        reads = [(int(self.rng.integers(0, size - 1)),
                  int(self.rng.integers(1, 300_000))) for _ in range(n)]
        reads += [(size - 1000, 4096), (size - 1, 1), (size, 16)]
        for off, ln in reads:
            got = self.fio.read(inode, off, ln)
            assert got == self.ref.read(inode, off, ln) \
                == self.blobs[0][off:off + ln], (self.tag, off, ln)


def rate(nbytes: int, dt: float) -> str:
    return f"{nbytes / MIB / dt:.0f} MiB/s"


def leg_cr3(ctx) -> str:
    files = Files(ctx, "cr3", "/smoke/cr3")
    ctx.cr3 = files
    t_w = files.write_all()
    for inode in files.inodes:
        assert set(inode.layout.chains) == set(CR_CHAINS), inode.layout
        assert inode.layout.chunk_size == CHUNK
    t_r = files.read_all("read-back")
    n_rand = 16 if ctx.rehearse else 300
    files.random_reads(n_rand)
    files.semantics_vs_inmem(4 if ctx.rehearse else 16)
    return (f"{files.nbytes // MIB} MiB on CR-3/1 MiB chunks: written in "
            f"{t_w:.1f}s ({rate(files.nbytes, t_w)}), read back exact in "
            f"{t_r:.1f}s ({rate(files.nbytes, t_r)}), {n_rand} random 4 KiB "
            f"reads, overwrite/short-read semantics = client/inmem.py "
            f"{ctx.dev_tag}")


def leg_ec(ctx) -> str:
    import numpy as np

    from tpu3fs.meta.types import Layout
    from tpu3fs.mgmtd.types import PublicTargetState
    from tpu3fs.ops import stripe
    from tpu3fs.ops.rs import RSCode
    from tpu3fs.storage.craq import ReadReq
    from tpu3fs.storage.types import ChunkId

    cluster = ctx.cluster
    files = Files(ctx, "ec", "/smoke/ec",
                  layout=Layout(table_id=2, chains=[EC_CHAIN],
                                chunk_size=CHUNK))
    snap = ctx.meter.snapshot()
    t_w = files.write_all()
    say(f"  wrote {files.nbytes // MIB} MiB in {t_w:.1f}s "
        f"({rate(files.nbytes, t_w)} {ctx.dev_tag}, first-call compiles "
        f"included); {ctx.meter.since(snap)}")
    t_r = files.read_all("read-back")
    files.semantics_vs_inmem(2 if ctx.rehearse else 8)   # sub-stripe RMW
    client = files.fio.storage
    assert client._ec_parity_rmw._value >= 1, "delta-parity RMW not engaged"
    S = stripe.shard_size_of(CHUNK, EC_K)
    codec = stripe.get_codec(EC_K, EC_M, S)
    assert not codec._use_host(), "the EC write never used the device codec"
    say(f"  read back exact in {t_r:.1f}s ({rate(files.nbytes, t_r)})")

    # (c) SIGKILL one storage process: 4 of 16 shards gone
    victim = cluster.procs[f"storage{VICTIM}"]
    os.killpg(victim.pid, signal.SIGKILL)
    victim.wait()
    t0 = time.time()
    cluster.wait_routing(
        lambda r: all(
            t.public_state != PublicTargetState.SERVING
            for c in r.chains.values() for t in c.targets
            if r.node_of_target(t.target_id).node_id == VICTIM),
        f"node {VICTIM}'s targets out of SERVING", budget=60)
    cluster.admin.invalidate_routing()
    lost = [j for j in range(EC_K + EC_M)
            if cluster.admin.refresh_routing().chains[EC_CHAIN]
            .target_of_shard(j).public_state != PublicTargetState.SERVING]
    assert len(lost) == EC_M, lost
    say(f"  SIGKILL storage{VICTIM}: shards {lost} of {EC_K + EC_M} out of "
        f"SERVING after {time.time() - t0:.1f}s")
    deg0 = client._ec_degraded._value
    snap = ctx.meter.snapshot()
    t_deg = files.read_all("degraded read")
    degraded = client._ec_degraded._value - deg0
    assert degraded > 0, "no read took the degraded decode"
    files.random_reads(8 if ctx.rehearse else 64)
    say(f"  degraded reads exact in {t_deg:.1f}s ({rate(files.nbytes, t_deg)}"
        f" {ctx.dev_tag}; {degraded} stripes decoded on the device); "
        f"{ctx.meter.since(snap)}")
    t_cr = ctx.cr3.read_all("degraded read")
    ctx.cr3.random_reads(8 if ctx.rehearse else 64)
    say(f"  CR-3 reads with one replica of three chains gone: exact in "
        f"{t_cr:.1f}s")
    # a write while degraded must still be acknowledged and read back
    files.semantics_vs_inmem(2)
    ctx.cr3.semantics_vs_inmem(2)

    # (d) restart it EMPTY (lost disk); the storage processes' own resync
    # workers (host kernels: they are pinned to the cpu) rebuild it
    shutil.rmtree(cluster.data_dir(VICTIM))
    t0 = time.time()
    cluster.spawn_storage(VICTIM)
    cluster.wait_routing(
        lambda r: all(t.public_state == PublicTargetState.SERVING
                      for c in r.chains.values() for t in c.targets),
        "every target back to SERVING",
        budget=120 if ctx.rehearse else 600)
    t_sync = time.time() - t0
    cluster.admin.invalidate_routing()
    t_clean = files.read_all("post-rebuild read")
    ctx.cr3.read_all("post-rebuild read")
    # the rebuilt targets themselves hold the right shard bytes
    routing = cluster.admin.refresh_routing()
    chain = routing.chains[EC_CHAIN]
    rs = RSCode(EC_K, EC_M)
    probes = 0
    for fi in (0, len(files.blobs) - 1):
        blob, inode = files.blobs[fi], files.inodes[fi]
        for idx in (0, len(blob) // CHUNK - 1):
            data = np.frombuffer(
                blob[idx * CHUNK:(idx + 1) * CHUNK].ljust(EC_K * S, b"\0"),
                dtype=np.uint8).reshape(1, EC_K, S)
            want = np.concatenate([data, rs.encode_np(data)], axis=1)[0]
            for j in lost:
                t = chain.target_of_shard(j)
                node = routing.node_of_target(t.target_id)
                got = ctx.view.send(node.node_id, "read_rebuild", ReadReq(
                    EC_CHAIN, ChunkId(inode.id, idx), 0, -1, t.target_id))
                assert got.ok, (j, got.code)
                stored = bytes(got.data)
                assert stored == want[j].tobytes()[:len(stored)] and (
                    j >= EC_K or len(stored) == min(
                        S, max(0, CHUNK - j * S))), (fi, idx, j)
                probes += 1
    say(f"  restarted empty: all targets SERVING after {t_sync:.1f}s; "
        f"clean reads exact in {t_clean:.1f}s; {probes} rebuilt shards read "
        f"straight off the new targets match the numpy gold")
    for c in stripe._codecs.values():
        assert not c._use_host(), (c.k, c.m, c.shard_size)
    return (f"{files.nbytes // MIB} MiB on RS({EC_K},{EC_M})/16 targets with "
            f"encode+CRC on the device: write {rate(files.nbytes, t_w)}, "
            f"read {rate(files.nbytes, t_r)}, degraded read "
            f"{rate(files.nbytes, t_deg)} {ctx.dev_tag}; kill -> degraded -> "
            f"empty restart -> SERVING in {t_sync:.0f}s -> clean")


# --------------------------------------------------------------------------
# the legs into and out of HBM
# --------------------------------------------------------------------------

def leg_kvcache(ctx) -> str:
    import jax
    import numpy as np

    from tpu3fs.kvcache import KVCacheClient, PrefixBlockStore

    chip = jax.devices()[0]
    block_bytes = int(np.prod(KV_BLOCK_SHAPE)) * 2
    n_blocks = max(8, ctx.leg_bytes // block_bytes)
    per_seq = min(128, n_blocks // 4)
    n_seq = n_blocks // per_seq
    rng = np.random.default_rng([ctx.seed, 3])
    cache = KVCacheClient(ctx.view.meta, ctx.view.file_client(retry=ctx.retry),
                          root="/smoke/kvcache")
    store = PrefixBlockStore(cache, block_tokens=KV_BLOCK_TOKENS)

    def pages_of(s: int) -> list:
        prng = np.random.default_rng([ctx.seed, 4, s])
        raw = prng.integers(0, 1 << 16, (per_seq,) + KV_BLOCK_SHAPE,
                            dtype=np.uint16)
        return list(raw.view(np.float16))

    seqs = [rng.integers(0, 1 << 40, per_seq * KV_BLOCK_TOKENS).tolist()
            for _ in range(n_seq)]
    t0 = time.time()
    for s, toks in enumerate(seqs):
        assert store.append_blocks(toks, pages_of(s)) == per_seq
    t_put = time.time() - t0
    # every sequence lands in HBM before ANY of it is looked at: the later
    # gets reuse the transport's pooled receive buffers under the earlier
    # (asynchronous) host-to-device copies
    t0 = time.time()
    on_chip = [store.get_blocks(toks, device=chip) for toks in seqs]
    jax.block_until_ready(on_chip)
    t_get = time.time() - t0
    for s, blocks in enumerate(on_chip):
        want = pages_of(s)
        assert len(blocks) == per_seq and all(b is not None for b in blocks)
        for b, w in zip(blocks, want):
            assert b.devices() == {chip} and b.dtype == np.float16
            assert np.asarray(b).tobytes() == w.tobytes(), \
                f"kvcache block of sequence {s} differs after landing"
    nbytes = n_seq * per_seq * block_bytes
    return (f"{n_seq * per_seq} blocks x 128 KiB = {nbytes // MIB} MiB: put "
            f"in {t_put:.1f}s ({rate(nbytes, t_put)}), get_blocks(device=) "
            f"into HBM in {t_get:.1f}s ({rate(nbytes, t_get)} {ctx.dev_tag}),"
            f" fetched back exact after all gets")


def leg_ckpt(ctx) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from tpu3fs.ckpt import CheckpointLoader, CheckpointSaver

    chip = jax.devices()[0]
    unit = max(64, int((ctx.leg_bytes / (1 << 30)) ** 0.5 * 2048) // 64 * 64)
    key = jax.random.key(ctx.seed)

    def leaf(i, shape, dtype):
        return jax.device_put(jax.random.normal(
            jax.random.fold_in(key, i), shape, dtype), chip)

    # 256 MiB embedding + 3 blocks of 256 MiB at unit=2048: 1 GiB. Keys in
    # sorted order: the manifest keeps dict insertion order, tree_map (which
    # builds the restore template below) sorts, and the loader compares
    tree = {
        "blocks": [{
            "w_down": leaf(10 * b + 3, (4 * unit, 2 * unit), jnp.float32),
            "w_up": leaf(10 * b + 2, (unit, 8 * unit), jnp.bfloat16),
            "wq": leaf(10 * b + 1, (unit, 4 * unit), jnp.float32),
        } for b in range(1, 4)],
        "embed": leaf(0, (32 * unit, unit), jnp.bfloat16),
        "step": jnp.asarray(1234, jnp.int32),
    }
    leaves = jax.tree_util.tree_leaves(tree)
    assert all(x.devices() == {chip} for x in leaves)
    want = [np.asarray(x).tobytes() for x in leaves]
    nbytes = sum(len(w) for w in want)
    fio = ctx.view.file_client(retry=ctx.retry)
    t0 = time.time()
    CheckpointSaver(ctx.view.meta, fio, root="/smoke/ckpt").save(tree, 7)
    t_save = time.time() - t0
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=SingleDeviceSharding(chip)), tree)
    t0 = time.time()
    back = CheckpointLoader(ctx.view.meta, fio, root="/smoke/ckpt").restore(
        7, like=like)
    jax.block_until_ready(back)
    t_load = time.time() - t0
    got = jax.tree_util.tree_leaves(back)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    for x, g, w in zip(leaves, got, want):
        assert isinstance(g, jax.Array) and g.devices() == {chip}
        assert g.shape == x.shape and g.dtype == x.dtype
        assert np.asarray(g).tobytes() == w, "restored leaf differs"
    return (f"{len(leaves)} leaves, {nbytes // MIB} MiB resident on the chip:"
            f" saved in {t_save:.1f}s ({rate(nbytes, t_save)}), restored onto"
            f" the chip in {t_load:.1f}s ({rate(nbytes, t_load)} "
            f"{ctx.dev_tag}), exact")


def _pack_records(ctx, path: str, n_records: int):
    import numpy as np

    from tpu3fs.dataload import pack_records

    rng = np.random.default_rng([ctx.seed, 5])
    tokens = rng.integers(0, 1 << 31, (n_records, RECORD_TOKENS),
                          dtype=np.int32)
    ctx.view.meta.mkdirs(path.rsplit("/", 1)[0], recursive=True)
    pack_records(ctx.view.meta, ctx.view.file_client(retry=ctx.retry), path,
                 [row.tobytes() for row in tokens])
    return tokens


def _stream_batches(ctx, path: str, tokens, mesh, n_batches: int,
                    global_batch: int) -> int:
    """DataLoader -> global jax.Array on the mesh -> a jitted step, each
    batch ended by block_until_ready; returns samples checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu3fs.dataload import DataLoader, LoaderConfig, PackedDataset

    ds = PackedDataset(ctx.view.meta, ctx.view.file_client(retry=ctx.retry),
                       [path])
    assert len(ds) == len(tokens)
    cfg = LoaderConfig(global_batch=global_batch, seed=ctx.seed, depth=2,
                       epochs=1, dtype="int32",
                       sample_shape=(RECORD_TOKENS,))

    @jax.jit
    def step(x):
        u = x.astype(jnp.uint32)
        return u.sum(axis=1), (u * jnp.uint32(2654435761)).max(axis=1)

    seen = 0
    with DataLoader(ds, cfg, mesh=mesh) as loader:
        for _ in range(n_batches):
            batch = next(loader)
            assert isinstance(batch.data, jax.Array)
            assert batch.data.sharding == NamedSharding(mesh, P("dp"))
            sums, maxes = jax.block_until_ready(step(batch.data))
            ref = tokens[batch.ids].astype(np.uint32)
            assert np.array_equal(np.asarray(batch.data), tokens[batch.ids])
            assert np.array_equal(np.asarray(sums),
                                  ref.sum(axis=1, dtype=np.uint32))
            assert np.array_equal(
                np.asarray(maxes),
                (ref * np.uint32(2654435761)).max(axis=1))
            seen += len(batch.ids)
    return seen


def leg_dataload(ctx) -> str:
    import jax

    from tpu3fs.parallel.mesh import make_storage_mesh

    n_records = 256 if ctx.rehearse else 2048
    batch = 16 if ctx.rehearse else 64
    path = "/smoke/data/train.rec"
    t0 = time.time()
    ctx.tokens = _pack_records(ctx, path, n_records)
    t_pack = time.time() - t0
    mesh = make_storage_mesh(1, devices=jax.devices()[:1])
    t0 = time.time()
    seen = _stream_batches(ctx, path, ctx.tokens, mesh, 8, batch)
    return (f"{n_records} x 32 KiB records packed in {t_pack:.1f}s; 8 "
            f"shuffled batches of {batch} ({seen} samples) streamed into a "
            f"jitted step on the chip in {time.time() - t0:.1f}s, exact")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def section_fourchip(ctx) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu3fs.ckpt import CheckpointLoader, CheckpointSaver
    from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
    from tpu3fs.ops.crc32c import BatchCrc32c
    from tpu3fs.ops.rs import RSCode
    from tpu3fs.parallel.chain import chain_write_step
    from tpu3fs.parallel.rebuild import rebuild_lost_shard
    from tpu3fs.parallel.shuffle import shuffle_partitions
    from tpu3fs.storage.types import ChunkId

    devs = jax.devices()[:4]
    rng = np.random.default_rng([ctx.seed, 6])
    size = 64 * 1024 if ctx.rehearse else MIB
    chain_mesh = Mesh(np.array(devs).reshape(1, 4), ("dp", "chain"))
    dp_mesh = Mesh(np.array(devs).reshape(4, 1), ("dp", "chain"))
    grid_mesh = Mesh(np.array(devs).reshape(2, 2), ("dp", "chain"))
    done = []

    # 1. CRAQ write over the ring, BatchCrc32c as the cross-check
    crc = BatchCrc32c(size, block=512)
    data = rng.integers(0, 256, (4, size), dtype=np.uint8)
    replicas, ok = jax.jit(
        lambda d: chain_write_step(chain_mesh, d, crc_fn=crc.compute))(data)
    replicas = np.asarray(jax.block_until_ready(replicas))
    assert replicas.shape == (4, 4, size) and np.asarray(ok).all()
    for pos in range(4):
        assert np.array_equal(replicas[pos], data), f"chain position {pos}"
    done.append(f"chain_write_step chain=4 crc=BatchCrc32c {size} B payloads")

    # 2. RS(3,1) rebuild over chain=4
    rs = RSCode(3, 1)
    sdata = rng.integers(0, 256, (4, 3, size), dtype=np.uint8)
    shards = np.moveaxis(
        np.concatenate([sdata, rs.encode_np(sdata)], axis=1), 1, 0).copy()
    for lost in (2, 3):
        broken = shards.copy()
        broken[lost] = 0
        rebuilt = np.asarray(rebuild_lost_shard(
            chain_mesh, jnp.asarray(broken), rs, [lost]))
        assert np.array_equal(rebuilt[0], shards[lost]), f"rebuild {lost}"
    done.append("rebuild_lost_shard RS(3,1) chain=4 (data and parity loss)")

    # 3. shuffle over dp=4
    part = np.zeros((16, 2, size), dtype=np.uint8)
    for src in range(4):
        for dst in range(4):
            part[src * 4 + dst] = rng.integers(0, 256, (2, size))
    out = np.asarray(shuffle_partitions(dp_mesh, jnp.asarray(part)))
    for dst in range(4):
        for src in range(4):
            assert np.array_equal(out[dst * 4 + src], part[src * 4 + dst])
    done.append("shuffle_partitions dp=4")

    # 4. the ICI serving mode: four replicas on one node, against the
    # messenger path, byte for byte
    def committed(transport, mesh=None):
        fab = Fabric(SystemSetupConfig(
            num_storage_nodes=1, num_chains=2, num_replicas=4,
            chunk_size=CHUNK, chain_transport=transport, mesh=mesh))
        client = fab.storage_client()
        wrng = np.random.default_rng([ctx.seed, 7])
        ops = [(fab.chain_ids[i % 2], ChunkId(31, i), 0,
                wrng.bytes(CHUNK - 4096 * i)) for i in range(8)]
        assert all(r.ok for r in client.batch_write(ops, chunk_size=CHUNK))
        assert client.write_chunk(fab.chain_ids[0], ChunkId(31, 0), 500,
                                  b"Z" * 300, chunk_size=CHUNK).ok
        state = {}
        for node in fab.nodes.values():
            for t in node.service.targets():
                for md in t.engine.all_metadata():
                    state[(t.target_id, md.chunk_id.to_bytes())] = (
                        md.committed_ver, md.checksum.value, md.length,
                        bytes(t.engine.read(md.chunk_id)))
        svc = next(iter(fab.nodes.values())).service
        hits = svc._ici.hits if transport == "ici" else 0
        fab.close()
        return state, hits

    s_ici, hits = committed("ici", chain_mesh)
    s_msg, _ = committed("messenger")
    assert hits > 0, "the collective path never served"
    assert s_ici == s_msg, "ICI chain state differs from the messenger's"
    done.append(f"Fabric(chain_transport='ici') 4 replicas/1 node: {hits} "
                f"batches over the collective, state = messenger path")

    # 5. checkpoint saved sharded 4-way, restored resharded 2x2
    fio = ctx.view.file_client(retry=ctx.retry)
    w = rng.standard_normal((4096, 512)).astype(np.float32)
    tree = {"w": jax.device_put(w, NamedSharding(dp_mesh, P("dp", None)))}
    CheckpointSaver(ctx.view.meta, fio, root="/smoke/ckpt4").save(tree, 1)
    like = {"w": jax.ShapeDtypeStruct(
        w.shape, w.dtype, sharding=NamedSharding(grid_mesh,
                                                 P("dp", "chain")))}
    back = CheckpointLoader(ctx.view.meta, fio, root="/smoke/ckpt4").restore(
        1, like=like)
    assert back["w"].sharding == like["w"].sharding
    assert len(back["w"].devices()) == 4
    assert np.array_equal(np.asarray(back["w"]), w)
    done.append("ckpt restore resharded 4 -> 2x2")

    # 6. DataLoader at dp=4
    path = "/smoke/data4/train.rec"
    tokens = _pack_records(ctx, path, 256)
    seen = _stream_batches(ctx, path, tokens, dp_mesh, 4, 32)
    done.append(f"DataLoader dp=4 ({seen} samples)")
    for line in done:
        say(f"  {line}: ok")
    return (f"{len(done)} mesh paths on {len(devs)} "
            f"{devs[0].platform} devices")


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run(args) -> int:
    if not __debug__:
        sys.exit("chip_smoke: run without -O; its checks are assert "
                 "statements")
    if not os.path.isdir(os.path.join(HERE, "tpu3fs")):
        sys.exit("chip_smoke: the tpu3fs package is not next to "
                 "chip_smoke.py; nothing to drive")
    sections = [s for s in SECTIONS
                if args.sections is None or s in args.sections]
    if "ec" in sections and "cr3" not in sections:
        sys.exit("chip_smoke: the ec section re-reads the cr3 files")
    ctx = types.SimpleNamespace()
    ctx.rehearse = args.rehearse_cpu
    ctx.seed = args.seed
    full_mib = 8 if ctx.rehearse else 1024
    size_mib = args.size_mib or full_mib
    ctx.leg_bytes = size_mib * MIB
    cut = sections != list(SECTIONS) or size_mib < full_mib
    if ctx.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        say("REHEARSAL on the cpu backend: NOT a chip run. It checks this "
            "script at a tiny size; it prints no result line.")
    t_start = time.time()
    jax, device, meter = require_device(ctx.rehearse)
    ctx.meter = meter
    ctx.dev_tag = (f"[{device['platform']} {device['kind']}]"
                   if not ctx.rehearse else "[cpu REHEARSAL]")
    if cut:
        say(f"CUT RUN: sections={','.join(sections)} "
            f"size={size_mib} MiB per leg (full: {full_mib})")

    # the device codec in THIS process only, through the existing switch;
    # a rehearsal has no device to ask for and flips the codecs directly
    from tpu3fs.ops import stripe

    if ctx.rehearse:
        for k, m, S, _ in REHEARSAL_KERNEL_SHAPES + (
                (EC_K, EC_M, stripe.shard_size_of(CHUNK, EC_K), "ec leg"),):
            stripe.get_codec(k, m, S)._host_mode = False
    else:
        os.environ["TPU3FS_STRIPE_DEVICE"] = "1"
    build_native()

    status = {s: "not run" for s in SECTIONS}
    cluster = None
    run_dir = args.run_dir or tempfile.mkdtemp(prefix="tpu3fs-smoke-")
    os.makedirs(run_dir, exist_ok=True)

    def section(name, fn):
        say(f"== {name} ==")
        snap, t0 = meter.snapshot(), time.time()
        detail = fn(ctx)
        status[name] = "passed"
        say(f"[{name}] PASS in {time.time() - t0:.1f}s: {detail}")
        say(f"[{name}] {meter.since(snap)}")
        if cluster is not None:
            cluster.verify_one_owner(ctx.rehearse)

    def on_term(signum, _frame):
        raise SystemExit(f"chip_smoke: signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    try:
        if "kernels" in sections:
            section("kernels", section_kernels)
        if any(s in NEED_CLUSTER for s in sections):
            from tpu3fs.cli import RpcFabricView
            from tpu3fs.client.storage_client import RetryOptions

            say("== cluster ==")
            t0 = time.time()
            cluster = Cluster(run_dir)
            ctx.cluster = cluster
            detail = cluster.boot()
            ctx.view = RpcFabricView(("127.0.0.1", cluster.mport),
                                     client_id="smoke")
            ctx.retry = RetryOptions(max_retries=12, backoff_base_s=0.05,
                                     backoff_max_s=0.5)
            say(f"[cluster] up in {time.time() - t0:.1f}s: {detail}")
            say(f"[cluster] {cluster.verify_one_owner(ctx.rehearse)}")
        for name, fn in (("cr3", leg_cr3), ("ec", leg_ec),
                         ("kvcache", leg_kvcache), ("ckpt", leg_ckpt),
                         ("dataload", leg_dataload)):
            if name in sections:
                section(name, fn)
        if "fourchip" in sections:
            if device["count"] >= 4:
                section("fourchip", section_fourchip)
            else:
                say(f"== fourchip ==\n[fourchip] NOT RUN: {device['count']} "
                    f"device(s) here, the mesh section needs 4")
        if cluster is not None:
            say(f"[cluster] at the end: "
                f"{cluster.verify_one_owner(ctx.rehearse)}")
    except BaseException:
        if cluster is not None:
            sys.stderr.write(cluster.log_tails() + "\n")
        raise
    finally:
        if cluster is not None:
            cluster.stop()
        if not args.run_dir:
            shutil.rmtree(run_dir, ignore_errors=True)

    say(f"seed {ctx.seed}; sections: "
        + ", ".join(f"{s}={status[s]}" for s in SECTIONS))
    say(f"total {time.time() - t_start:.0f}s; "
        f"{meter.since((0, 0.0, 0, 0, 0))}")
    if ctx.rehearse:
        say("REHEARSAL finished on the cpu backend: not a chip run, "
            "no result.")
        return 0
    if cut:
        say("CUT RUN finished on the chip: not the whole smoke, no result.")
        return 0
    print(result_line(device), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="every byte written is generated from this")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny run on the cpu backend; not a chip run, "
                        "prints no result line")
    p.add_argument("--sections", type=lambda s: s.split(","),
                   help=f"subset of {','.join(SECTIONS)}; a cut run prints "
                        "no result line")
    p.add_argument("--size-mib", type=int, default=0,
                   help="MiB per leg instead of 1024; a cut run prints no "
                        "result line")
    p.add_argument("--run-dir", default="",
                   help="where the cluster keeps its disks and logs "
                        "(default: a fresh temporary directory, removed)")
    args = p.parse_args(argv)
    if args.sections:
        unknown = set(args.sections) - set(SECTIONS)
        if unknown:
            p.error(f"unknown sections {sorted(unknown)}")
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
