"""The cluster a run measures against: mgmtd + storage processes + meta,
started through tpu3fs.bin.* as real CPU-pinned processes, while this
process is the client and the one owner of the chip.

A COPY of chip_smoke.py's Cluster, child_env, free_port, build_native and
require_device (PR 21, proven on the chip), taken so that later PRs may
change the smoke and may not change the yardstick. What differs: the chain
layout comes from the configuration's file, `make` is not forced (only a
checkout's first run builds), children get SIGTERM and a grace period
before SIGKILL, and what this run's own processes left in /dev/shm is
removed and counted.
"""

from __future__ import annotations

import os
import signal
import socket
import struct
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SHM_DIR = "/dev/shm"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def child_env() -> dict:
    """Every service child is pinned to the CPU backend and never sees the
    switch that sends a process's stripe codec to the device, nor a compile
    cache: the chip has one owner, this process."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("TPU3FS_STRIPE_DEVICE", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class CompileMeter:
    """JAX's own compile events, so that a compile inside the measured
    window, or a second run that misses the persistent cache, shows."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration


def require_device(chips: int, rehearse: bool):
    """Assert the accelerator and turn the compile cache on.
    -> (jax, device dict, CompileMeter). No chip, no number."""
    sys.path.insert(0, ROOT)
    from tpu3fs.utils.compile_cache import enable_compile_cache

    import jax

    cache = enable_compile_cache()
    meter = CompileMeter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__}; compile cache: "
        f"{cache or 'off'}")
    if rehearse:
        if device["platform"] != "cpu":
            sys.exit("perfbench: --rehearse-cpu wants JAX_PLATFORMS=cpu")
    elif device["platform"] != "tpu":
        sys.exit(f"perfbench: no TPU (jax's default backend is "
                 f"{device['platform']!r}); nothing ran, no result")
    elif device["count"] < chips:
        sys.exit(f"perfbench: the cell asks for {chips} chip(s), jax "
                 f"finds {device['count']}; nothing ran, no result")
    return jax, device, meter


def build_native() -> float:
    """native/*.so is git-ignored: build from the committed sources before
    any child starts (plain make: only a checkout's first run compiles)
    and prove both libraries load."""
    t0 = time.time()
    subprocess.run(["make", "-j4", "-C", os.path.join(ROOT, "native")],
                   check=True, stdout=subprocess.DEVNULL)
    from tpu3fs.ops import native_ec
    from tpu3fs.ops.crc32c import _native_crc
    from tpu3fs.rpc import native_net

    if not (native_ec.available() and _native_crc() is not None
            and native_net._load_lib() is not None):
        sys.exit("perfbench: the native libraries did not load")
    return time.time() - t0


def service_processes(run_dir: str = "") -> list:
    """Pids of tpu3fs.bin.* service processes (by argv, not by a pattern
    that a shell's own command line would match); with run_dir, only those
    started in that run directory."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
            if not any(a.startswith(b"tpu3fs.bin.") for a in argv):
                continue
            if run_dir:
                cwd = os.readlink(f"/proc/{pid}/cwd")
                if not cwd.startswith(run_dir):
                    continue
        except OSError:
            continue
        out.append(int(pid))
    return out


def read_target(view, routing, chain_id: int, chunk_id, target_id: int):
    """One target's own copy of a chunk, asked of the node that holds it (not
    the chain's answer to a client). -> its bytes, or None where the target
    has no good copy."""
    from tpu3fs.storage.craq import ReadReq

    node = routing.node_of_target(target_id)
    got = view.send(node.node_id, "read_rebuild", ReadReq(
        chain_id, chunk_id, 0, -1, target_id))
    return bytes(got.data) if got.ok else None


def shm_entries() -> set:
    try:
        return {n for n in os.listdir(SHM_DIR) if "tpu3fs" in n}
    except OSError:
        return set()


def shm_mapped_by(pids) -> set:
    """The program's /dev/shm names (rings, buffers, their semaphores) that
    one of these processes has mapped, from /proc/<pid>/maps."""
    names = set()
    for pid in pids:
        try:
            with open(f"/proc/{pid}/maps") as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        for line in lines:
            _, sep, name = line.partition(SHM_DIR + "/")
            if sep and "tpu3fs" in name:
                names.add(name.removesuffix(" (deleted)"))
    return names


def shm_owner(name: str) -> int:
    """The pid the program itself stamps on a /dev/shm entry: a ring's
    header ends with its owner's pid (docs/usrbio_abi.md: eight little-
    endian fields, the last two 32-bit), a handshake nonce is named
    tpu3fs-hs-<pid>-<hex>. -> 0 where the entry names no pid."""
    try:
        if name.startswith("tpu3fs-hs-"):
            return int(name.split("-")[2])
        if name.startswith("tpu3fs-ior-"):
            with open(os.path.join(SHM_DIR, name), "rb") as f:
                head = f.read(48)
            return struct.unpack("<IIQQQQII", head)[7]
    except (OSError, ValueError, IndexError, struct.error):
        pass
    return 0


class Cluster:
    """Boots what the configuration's `cluster` section describes."""

    # `python -m module` preceded by PR_SET_PDEATHSIG(SIGKILL): the kernel
    # kills the child if this process dies first. Set by the child itself
    # because a preexec_fn would fork() a process full of JAX threads.
    _LAUNCH = ("import ctypes, runpy, signal, sys; "
               "ctypes.CDLL(None).prctl(1, signal.SIGKILL); "
               "sys.argv = sys.argv[1:]; "
               "runpy.run_module(sys.argv[0], run_name='__main__', "
               "alter_sys=True)")

    def __init__(self, run_dir: str, spec: dict):
        self.run_dir = run_dir
        self.spec = spec
        self.logs = os.path.join(run_dir, "logs")
        os.makedirs(self.logs, exist_ok=True)
        self.env = child_env()
        self.procs: dict = {}
        self.mport = free_port()
        self.admin = None
        self.nodes = [101 + i for i in range(int(spec["storage_nodes"]))]

    def spawn(self, name: str, module: str, *args: str) -> None:
        with open(os.path.join(self.logs, f"{name}.log"), "ab") as log:
            self.procs[name] = subprocess.Popen(
                [sys.executable, "-c", self._LAUNCH, module, *args],
                env=self.env, cwd=self.run_dir, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def spawn_storage(self, nid: int) -> None:
        self.spawn(
            f"storage{nid}", "tpu3fs.bin.storage_main",
            "--node-id", str(nid), "--mgmtd", f"127.0.0.1:{self.mport}",
            "--heartbeat_interval", "0.3",
            f"--config.engine={self.spec.get('engine', 'native')}",
            f"--config.data_dir={os.path.join(self.run_dir, f'storage_{nid}')}",
            "--config.target_scan_interval_s=0.3",
            "--config.resync_interval_s=0.3")

    def stop(self, grace_s: float = 5.0) -> dict:
        """SIGTERM, a grace period, then SIGKILL; wait for every child;
        remove what THIS run's processes left in /dev/shm: only entries one
        of them had mapped or is stamped as the owner of, never another
        run's or a test's beside this one. -> what was found."""
        alive = [p for p in self.procs.values() if p.poll() is None]
        pids = {p.pid for p in self.procs.values()} | {os.getpid()}
        mapped = shm_mapped_by(pids)
        for p in alive:
            try:
                os.killpg(p.pid, signal.SIGTERM)
            except OSError:
                pass
        deadline = time.time() + grace_s
        for p in alive:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                pass
        killed = 0
        for p in alive:
            if p.poll() is None:
                killed += 1
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        left = sorted(n for n in shm_entries()
                      if n in mapped or shm_owner(n) in pids)
        for name in left:
            try:
                os.unlink(os.path.join(SHM_DIR, name))
            except OSError:
                pass
        return {"children": len(self.procs), "sigkilled": killed,
                "still_running": sum(p.poll() is None
                                     for p in self.procs.values())
                + len(service_processes(self.run_dir)),
                "shm_removed": len(left)}

    def log_tails(self, lines: int = 12) -> str:
        out = []
        for name in sorted(os.listdir(self.logs)):
            with open(os.path.join(self.logs, name), "rb") as f:
                tail = f.read()[-4000:].decode("utf-8", "replace")
            kept = [ln for ln in tail.splitlines()
                    if "hugepages" not in ln and "warnings.warn" not in ln]
            out.append(f"--- {name} ---\n" + "\n".join(kept[-lines:]))
        return "\n".join(out)

    def wait_routing(self, pred, what: str, budget: float = 60.0):
        deadline = time.time() + budget
        while time.time() < deadline:
            routing = self.admin.refresh_routing()
            if pred(routing):
                return routing
            for name, p in self.procs.items():
                if p.poll() is not None:
                    raise RuntimeError(f"{name} died while waiting for "
                                       f"{what}")
            time.sleep(0.2)
        raise RuntimeError(f"timeout ({budget:.0f}s) waiting for {what}")

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
                if time.time() > deadline:
                    raise RuntimeError("mgmtd never listened")
                if self.procs["mgmtd"].poll() is not None:
                    raise RuntimeError("mgmtd died")
                time.sleep(0.2)
        for nid in self.nodes:
            self.spawn_storage(nid)
        self.admin = MgmtdAdminRpcClient(("127.0.0.1", self.mport))
        n = len(self.nodes)
        tid = 1
        for table in self.spec["tables"]:
            chain_ids = []
            for c, chain in enumerate(table["chains"]):
                width = int(chain["targets"])
                # a chain narrower than the cluster skips node c (CR-3 on
                # four nodes: three replicas on the three other nodes); a
                # wider one puts shard j on node j % n
                skew = 1 if width < n else 0
                tids = []
                for r in range(width):
                    self.admin.create_target(
                        tid, node_id=self.nodes[(c + r + skew) % n])
                    tids.append(tid)
                    tid += 1
                kw = {}
                if chain.get("ec_k"):
                    kw = {"ec_k": int(chain["ec_k"]),
                          "ec_m": int(chain["ec_m"])}
                self.admin.upload_chain(int(chain["chain_id"]), tids, **kw)
                chain_ids.append(int(chain["chain_id"]))
            self.admin.upload_chain_table(int(table["table_id"]), chain_ids)
        n_targets = tid - 1
        self.wait_routing(
            lambda r: len(r.targets) == n_targets and all(
                t.local_state == LocalTargetState.UPTODATE
                for t in r.targets.values()),
            f"{n_targets} targets UPTODATE", budget=120)
        self.spawn("meta", "tpu3fs.bin.meta_main", "--node-id", "201",
                   "--mgmtd", f"127.0.0.1:{self.mport}",
                   "--heartbeat_interval", "0.3", *self.spec["meta_args"])
        self.wait_routing(
            lambda r: any(nd.type == NodeType.META and nd.host
                          for nd in r.nodes.values()),
            "meta server registered", budget=90)
        return (f"mgmtd + {n} storage + meta; {n_targets} targets in "
                f"{sum(len(t['chains']) for t in self.spec['tables'])} "
                f"chain(s)")

    # -- who holds the chip -------------------------------------------------
    @staticmethod
    def _chip_marks(pid: int) -> list:
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
        if not rehearse and not mine:
            raise RuntimeError("this process holds the chip yet shows no "
                               "mark of it in /proc: the check is blind")
        for name, p in self.procs.items():
            if p.poll() is not None:
                raise RuntimeError(f"{name} is not running")
            with open(f"/proc/{p.pid}/environ", "rb") as f:
                env = dict(kv.split(b"=", 1) for kv in f.read().split(b"\0")
                           if b"=" in kv)
            if env.get(b"JAX_PLATFORMS") != b"cpu":
                raise RuntimeError(f"{name} is not pinned to the cpu")
            if b"TPU3FS_STRIPE_DEVICE" in env:
                raise RuntimeError(f"{name} has the device-codec switch")
            marks = self._chip_marks(p.pid)
            if marks:
                raise RuntimeError(f"{name} touched the chip: {marks}")
        return (f"{len(self.procs)} children pinned to the cpu, none "
                f"touched the chip; this process: "
                f"{', '.join(mine) or 'cpu rehearsal'}")
