"""One run of one cell: set-up, the measured window, the comparison that
decides `correct`, and the result line.

Driven by data. BENCHMARK.json says which cells and metrics exist; the cell,
its configuration, its traffic mix and every metric are files found by
name. Nothing here names a cell, a configuration or a metric.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import sys
import tempfile
import time
import types

from . import cluster as cl
from .proxies import SpanLog, TimingProxy

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    """A mix's data file. A mix that `extends` another takes that one's
    driver, parameters and rehearsal sizes and states only what differs, so
    that two mixes over one corpus cannot drift apart."""
    mix = load_json("traffic", f"{name}.json")
    if "extends" in mix:
        base = load_traffic(mix["extends"])
        mix = {**base, **mix,
               "params": {**base["params"], **mix.get("params", {})},
               "rehearsal": {**base.get("rehearsal", {}),
                             **mix.get("rehearsal", {})}}
    return mix


class Catalog:
    """BENCHMARK.json and the data files it names."""

    def __init__(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)

    def cell(self, name: str) -> dict:
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            sys.exit(f"perfbench: BENCHMARK.json has no workload {name!r}; "
                     f"it has {sorted(cells)}")
        entry = cells[name]
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            entry["config"]]
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            config = json.load(f)
        traffic = load_traffic(entry["traffic"])
        return {"name": name, "chips": int(entry["chips"]),
                "config": config, "traffic": traffic,
                "end_to_end": self._metrics("end_to_end", name),
                "per_layer": self._metrics("per_layer", name)}

    def _metrics(self, group: str, cell: str) -> list:
        out = []
        for m in self.bench[group]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            spec = load_json("metrics", f"{m['name']}.json")
            out.append({**m, "reader": spec["reader"],
                        "args": spec.get("args", {})})
        return out


def read_metrics(metrics: list, run) -> dict:
    """Each metric through its reader; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in metrics:
        reader = importlib.import_module(f"perfbench.readers.{m['reader']}")
        value = reader.read(run, m["args"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Check:
    """One number compared beside its limit (`value` <= `limit` passes)."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, value, limit

    @property
    def ok(self) -> bool:
        return self.value <= self.limit

    def pair(self) -> dict:
        return {"value": self.value, "limit": self.limit}


def probe_ready(view, budget_s: float = 60.0) -> int:
    """A cluster whose meta server has just registered can still refuse its
    first create-with-truncate (the meta server's own storage client has no
    node for the chain's head yet: INTERNAL, seen on CR chains right after
    boot). Booting ends when one whole create, write, read and remove has
    gone through. -> how many probes it took."""
    from tpu3fs.meta.store import OpenFlags
    from tpu3fs.utils.result import FsError

    fio = view.file_client()
    deadline = time.time() + budget_s
    n = 0
    view.meta.mkdirs("/probe", recursive=True)
    try:
        while True:
            n += 1
            try:
                res = view.meta.create(
                    f"/probe/p{n}", flags=OpenFlags.WRITE | OpenFlags.CREATE
                    | OpenFlags.TRUNC)
                fio.write(res.inode, 0, b"ready?" * 100)
                view.meta.close(res.inode.id, res.session_id,
                                length_hint=600, wrote=True)
                inode = view.meta.stat(f"/probe/p{n}")
                if bytes(fio.read(inode, 0, 600)) == b"ready?" * 100:
                    view.meta.remove(f"/probe/p{n}")
                    return n
            except FsError as e:
                if time.time() > deadline:
                    raise RuntimeError(f"the cluster never served a whole "
                                       f"create-write-read: {e!r}")
            time.sleep(0.2)
    finally:
        fio.storage.close()


def parse(argv):
    p = argparse.ArgumentParser(prog="perfbench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the cpu backend, Pallas interpreted; "
                        "checks the harness, prints NO result line")
    p.add_argument("--fault", default="",
                   help="plant a named fault (faults/<name>.py) under the "
                        "timed path: the controls and the tests use it")
    return p.parse_args(argv)


def run(argv, t_start: float) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "tpu3fs")):
        sys.exit("perfbench: the tpu3fs package is not beside perfbench/; "
                 "nothing to measure, no result")
    sys.path.insert(0, ROOT)
    catalog = Catalog()
    cell = catalog.cell(args.workload)
    rehearse = args.rehearse_cpu
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cl.say("REHEARSAL on the cpu backend: not a chip run, prints no "
               "result line")
    seconds = args.seconds if args.seconds is not None else float(
        catalog.bench["run_seconds"])
    jax, device, meter = cl.require_device(cell["chips"], rehearse)
    config, traffic = cell["config"], cell["traffic"]
    params = dict(traffic["params"])
    if rehearse:
        params.update(traffic.get("rehearsal", {}))

    from tpu3fs.ops import stripe

    if config.get("device_codec"):
        if rehearse:
            for chain in (c for t in config["cluster"]["tables"]
                          for c in t["chains"] if c.get("ec_k")):
                stripe.get_codec(
                    chain["ec_k"], chain["ec_m"], stripe.shard_size_of(
                        config["chunk_size"], chain["ec_k"])
                )._host_mode = False
        else:
            os.environ["TPU3FS_STRIPE_DEVICE"] = "1"
    t_native = cl.build_native()

    run_dir = tempfile.mkdtemp(prefix="perfbench-")
    ctx = types.SimpleNamespace(
        seed=args.seed, params=params, config=config, rehearse=rehearse,
        trace=bool(args.trace), jax=jax, chip=jax.devices()[0],
        device=device, meter=meter, spans=SpanLog(), run_dir=run_dir,
        fault=args.fault, say=cl.say, codec_calls=[], requests=[],
        counters={}, t_start=t_start, window=None, trace_data=None,
        after_window=[], in_window=False)
    ctx.window_open = lambda: ctx.in_window
    ctx.wrap = (lambda obj, layer: TimingProxy(obj, layer, ctx.spans)) \
        if args.trace else (lambda obj, layer: obj)

    def on_term(signum, _frame):
        raise SystemExit(f"perfbench: signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    cluster = None
    driver = None
    left = {}
    try:
        from tpu3fs.cli import RpcFabricView
        from tpu3fs.client.storage_client import RetryOptions

        t0 = time.time()
        cluster = cl.Cluster(run_dir, config["cluster"])
        ctx.cluster = cluster
        detail = cluster.boot()
        ctx.new_view = lambda tag: RpcFabricView(
            ("127.0.0.1", cluster.mport), client_id=f"pb{args.seed}{tag}")
        ctx.view = ctx.new_view("")
        ctx.retry = RetryOptions(max_retries=12, backoff_base_s=0.05,
                                 backoff_max_s=0.5)
        probes = probe_ready(ctx.view)
        cl.say(f"[cluster] first whole create-write-read after {probes} "
               f"probe(s)")
        cl.say(f"[cluster] up in {time.time() - t0:.1f}s (native libs "
               f"{t_native:.1f}s): {detail}")
        cl.say(f"[cluster] {cluster.verify_one_owner(rehearse)}")
        if args.fault:
            importlib.import_module(
                f"perfbench.faults.{args.fault}").plant(ctx)
            cl.say(f"FAULT planted under the timed path: {args.fault}")
        module = importlib.import_module(
            f"perfbench.drivers.{traffic['driver']}")
        driver = module.Driver(ctx)
        driver.setup()
        driver.warm()
        compiled_before = meter.programs
        ctx.setup_s = time.time() - t_start
        cl.say(f"[setup] {ctx.setup_s:.1f}s; compiled {meter.programs} "
               f"programs in {meter.seconds:.1f}s (persistent cache: "
               f"{meter.hits} hits, {meter.misses} misses)")

        trace_dir = os.path.join(run_dir, "trace")
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_w0 = time.perf_counter()
        ctx.in_window = True
        driver.window(seconds)
        ctx.in_window = False
        t_w1 = time.perf_counter()
        for hook in ctx.after_window:   # a planted control's, else none
            hook(ctx, driver)
        if args.trace:
            jax.profiler.stop_trace()
        ctx.window = (t_w0, t_w1)
        compiled_in_window = meter.programs - compiled_before
        stats = ctx.chip.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        cl.say(f"[window] {t_w1 - t_w0:.1f}s, {len(ctx.requests)} requests, "
               f"{compiled_in_window} programs compiled inside it")
        cl.say(f"[cluster] {cluster.verify_one_owner(rehearse)}")

        if args.trace:
            from . import trace as tr

            ctx.trace_data = tr.reduce(
                tr.load_xplane(tr.find_xplane(trace_dir), rehearse),
                t_w1 - t_w0)

        t_v0 = time.time()
        checks = list(driver.verify())
        checks.append(Check("compiled_in_window", compiled_in_window, 0))
        cl.say(f"[verify] {time.time() - t_v0:.1f}s")
    except BaseException:
        if cluster is not None:
            sys.stderr.write(cluster.log_tails() + "\n")
        raise
    finally:
        if driver is not None:
            try:
                driver.close()
            except Exception as e:  # closing must not hide the run's error
                cl.say(f"driver.close: {e!r}")
        if cluster is not None:
            left = cluster.stop()
        shutil.rmtree(run_dir, ignore_errors=True)
    cl.say(f"[exit] {left}")

    failed = sum(1 for r in ctx.requests if not r["ok"])
    checks.append(Check("failed_requests", failed, 0))
    checks.append(Check("children_left_running",
                        left.get("still_running", 0), 0))
    correct = all(c.ok for c in checks)
    metrics = read_metrics(
        cell["per_layer"] if args.trace else cell["end_to_end"], ctx)
    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": len(ctx.requests),
              "failed": failed, "metrics": metrics, "device": dev}
    if args.trace:
        dev["busy_s"] = ctx.trace_data["busy_s"]
        dev["window_s"] = ctx.trace_data["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace_data["device_ops"],
            "idle_gaps": ctx.trace_data["idle_gaps"]}
    if args.fault:
        result["fault"] = args.fault
    result["workload"] = cell["name"]
    result["seed"] = args.seed
    result["compared"] = {c.name: c.pair() for c in checks}
    for c in checks:
        cl.say(f"compared {c.name}: {c.value} (limit {c.limit})"
               f"{'' if c.ok else '  <-- FAILS'}")
    if rehearse:
        cl.say("REHEARSAL finished on the cpu backend (correct="
               f"{correct}); not a chip run, no result line. What a chip "
               f"run would have printed, for the eye only:")
        cl.say("  " + json.dumps(result)[:3000])
        return 0 if correct else 3
    print(json.dumps(result), flush=True)
    return 0
