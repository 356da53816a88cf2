"""Traffic on the checkpoint path: the configuration's state resident in
HBM, made on the chip from the seed; a cycle saves it as a new step,
restores that step onto the chip under a template, and one jitted step
changes every leaf, as training does, so that no two steps hold the same
bytes. Cycles start while the window is open; the last one started is
finished; none is judged half-done.

A request is one cycle. The reference is the same state worked out again
from the seed after the window: step^c(init(seed)) for cycle c.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

from ..lib import reference as ref
from ..lib.cluster import read_target
from ..lib.harness import Check


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.root = f"/ckpt/seed{ctx.seed}"
        self.shrink = int(ctx.params.get("shrink", 1))
        self.keep = int(ctx.params["keep_restored"])
        self.cycles: list = []    # per cycle: step, fingerprints, restored?
        self.storages: list = []

    # -- the state, from the seed -------------------------------------------
    def shapes(self) -> dict:
        """{leaf path: (shape, dtype name)}, keys sorted at every level."""
        state = self.ctx.config["state"]
        out = {}
        for t in state["tensors"]:
            shape = tuple(max(1, d // self.shrink) if len(t["shape"]) > 1
                          else d for d in t["shape"])
            for i in range(int(t["count"])):
                for copy, dtype in state["copies"].items():
                    out[f"{t['name']}/{i:02d}/{copy}"] = (shape, dtype)
        return out

    def build(self):
        """-> (init, step): jitted makers of the whole tree, one program
        each, on the chip, in the types the state is held in."""
        jax = self.ctx.jax
        import jax.numpy as jnp

        shapes = self.shapes()

        def nest(flat: dict) -> dict:
            tree: dict = {}
            for path in sorted(flat):
                name, i, copy = path.split("/")
                tree.setdefault(name, {}).setdefault(i, {})[copy] = flat[path]
            return tree

        def init(key):
            flat = {}
            for n, (path, (shape, dtype)) in enumerate(sorted(
                    shapes.items())):
                flat[path] = jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.dtype(dtype))
            return nest(flat)

        def step(tree, c):
            # every leaf changes, by a different amount each cycle
            scale = (1.0 + 0.001 * (c + 1)).astype(jnp.float32)
            return jax.tree_util.tree_map(
                lambda x: (x.astype(jnp.float32) * scale
                           + 0.001 * (c + 1)).astype(x.dtype), tree)

        return jax.jit(init), jax.jit(step)

    def template(self, tree):
        """Keys inserted in sorted order: the loader compares the manifest's
        insertion order with this one's (recorded in CHANGES.md, PR 21)."""
        jax = self.ctx.jax
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(self.ctx.chip)

        def walk(node):
            if isinstance(node, dict):
                return {k: walk(node[k]) for k in sorted(node)}
            return jax.ShapeDtypeStruct(node.shape, node.dtype,
                                        sharding=sharding)

        return walk(tree)

    def make_state(self):
        """The state as made from the seed, committed to the chip as a
        restored tree is: a jitted program is compiled for committed and
        for uncommitted arguments apart, and the window's are committed."""
        from jax.sharding import SingleDeviceSharding

        jax = self.ctx.jax
        return jax.block_until_ready(jax.device_put(
            self.init(self.key), SingleDeviceSharding(self.ctx.chip)))

    def fingerprints(self, tree):
        from ..lib.device import fingerprint

        return [fingerprint(x) for x in
                self.ctx.jax.tree_util.tree_leaves(tree)]

    def setup(self) -> None:
        from tpu3fs.ckpt import CheckpointLoader, CheckpointSaver

        ctx, jax = self.ctx, self.ctx.jax
        self.init, self.step = self.build()
        self.key = jax.random.key(ctx.seed % (1 << 31))
        self.tree = self.make_state()
        leaves = jax.tree_util.tree_leaves(self.tree)
        self.nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
        ctx.say(f"[state] {len(leaves)} leaves, {self.nbytes >> 20} MiB "
                f"resident on the chip")
        # reckon the run directory's disk before writing to it
        replicas = int(ctx.config["cluster"]["tables"][0]["chains"][0][
            "targets"])
        free = shutil.disk_usage(ctx.run_dir).free
        need = self.nbytes * replicas * 6 * float(
            ctx.params["min_free_disk_factor"])
        if free < need:
            raise RuntimeError(f"{free >> 20} MiB free under {ctx.run_dir}, "
                               f"the cell may write {int(need) >> 20} MiB")
        view = ctx.new_view("ck")
        fio = view.file_client(retry=ctx.retry)
        self.storages.append(fio.storage)
        meta, fio = ctx.wrap(view.meta, "meta"), ctx.wrap(fio, "fio")
        self.saver = CheckpointSaver(meta, fio, root=self.root)
        self.loader = CheckpointLoader(meta, fio, root=self.root)
        self.like = self.template(self.tree)
        self.next_step = 1   # steps are never reused, in a run or across

    def cycle(self) -> None:
        ctx, jax = self.ctx, self.ctx.jax
        step_no = self.next_step
        self.next_step += 1
        c = len(self.cycles)
        rec = {"id": c, "ok": False, "load_bytes": 0, "store_bytes": 0,
               "phases": {}}
        ctx.spans.set_request(c)
        t0 = time.perf_counter()
        back = None
        try:
            with jax.profiler.TraceAnnotation("pb:ckpt.save"):
                self.saver.save(self.tree, step_no)
            t_saved = time.perf_counter()
            with jax.profiler.TraceAnnotation("pb:ckpt.restore"):
                back = self.loader.restore(step_no, like=self.like)
                jax.block_until_ready(back)
            t_back = time.perf_counter()
            fps = jax.block_until_ready(self.fingerprints(back))
            rec["phases"] = {"save": t_saved - t0,
                             "restore": t_back - t_saved}
            rec["store_bytes"] = rec["load_bytes"] = self.nbytes
            rec["ok"] = True
        except Exception as e:  # a failed cycle is a failed request
            rec["error"] = repr(e)
            ctx.say(f"cycle {c} (step {step_no}) FAILED: {e!r}")
            fps = []
        rec["t0"], rec["t1"] = t0, time.perf_counter()
        ctx.requests.append(rec)
        self.cycles.append({"step": step_no, "fps": fps, "back": back,
                            "ok": rec["ok"]})
        for old in self.cycles[:-self.keep]:
            old["back"] = None    # leaves HBM; its fingerprints stay
        # training goes on from what was restored, as after a preemption
        if rec["ok"]:
            self.tree = self.step(back, np.int32(c))

    def warm(self) -> None:
        """Compile the step and every leaf shape's fingerprint on the state
        itself, and take the state's smallest tensors (smallest first, up
        to the mix's `warm_bytes`, one at the least) through one save and
        restore under a root of their own, which opens every path to the
        cluster. The window's first cycle starts from the state as made
        from the seed."""
        from tpu3fs.ckpt import CheckpointLoader, CheckpointSaver

        ctx, jax = self.ctx, self.ctx.jax
        jax.block_until_ready(self.fingerprints(self.tree))
        jax.block_until_ready(self.step(self.tree, np.int32(0)))
        size = {k: sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(v))
                for k, v in self.tree.items()}
        small, room = {}, int(ctx.params["warm_bytes"])
        for k in sorted(size, key=lambda k: (size[k], k)):
            if small and size[k] > room:
                break
            small[k] = self.tree[k]
            room -= size[k]
        small = {k: small[k] for k in sorted(small)}
        meta, fio = self.saver._meta, self.saver._fio
        root = self.root + "-warm"
        CheckpointSaver(meta, fio, root=root).save(small, 1)
        back = CheckpointLoader(meta, fio, root=root).restore(
            1, like=self.template(small))
        jax.block_until_ready(back)

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.cycle()
            if not self.cycles[-1]["ok"]:
                break

    def verify(self):
        from tpu3fs.storage.types import ChunkId

        ctx, jax = self.ctx, self.ctx.jax
        self.tree = None
        want = self.make_state()
        fp_wrong = leaves_wrong = leaves_seen = 0
        last_ok = None
        for c, cyc in enumerate(self.cycles):
            if cyc["ok"]:
                want_fp = [tuple(int(v) for v in np.asarray(f))
                           for f in self.fingerprints(want)]
                got_fp = [tuple(int(v) for v in np.asarray(f))
                          for f in cyc["fps"]]
                fp_wrong += sum(1 for g, w in zip(got_fp, want_fp) if g != w)
                fp_wrong += abs(len(got_fp) - len(want_fp))
                if cyc["back"] is not None:
                    # byte for byte (random bits make NaNs: compare bytes)
                    for g, w in zip(jax.tree_util.tree_leaves(cyc["back"]),
                                    jax.tree_util.tree_leaves(want)):
                        leaves_seen += 1
                        if (g.shape != w.shape or g.dtype != w.dtype
                                or g.devices() != {ctx.chip}
                                or np.asarray(g).tobytes()
                                != np.asarray(w).tobytes()):
                            leaves_wrong += 1
                    if (jax.tree_util.tree_structure(cyc["back"])
                            != jax.tree_util.tree_structure(want)):
                        leaves_wrong += 1
                    cyc["back"] = None
                last_ok = (cyc["step"], want)
                want = self.step(want, np.int32(c))
        ctx.say(f"[verify] {len(self.cycles)} cycles by fingerprint of every "
                f"leaf, {leaves_seen} leaves byte for byte")
        checks = [Check("restored_fingerprints_wrong", fp_wrong, 0),
                  Check("restored_leaves_wrong", leaves_wrong, 0)]
        # the stored form of the last step: a sampled chunk of sampled
        # leaves' data files (named as docs/ckpt.md names them, not as the
        # program's manifest does) on all three replicas, against the
        # reference leaf's bytes
        replicas_wrong = replicas_seen = 0
        if last_ok is not None:
            step_no, tree = last_ok
            leaves = jax.tree_util.tree_leaves(tree)
            rng = np.random.default_rng([ctx.seed, 10])
            routing = ctx.cluster.admin.refresh_routing()
            want_replicas = int(ctx.config["cluster"]["tables"][0]["chains"][
                0]["targets"])
            for li in rng.permutation(len(leaves))[
                    :int(ctx.params["verify_chunks"])].tolist():
                gold = ref.as_unsigned(np.asarray(leaves[li])) \
                    .reshape(-1).view(np.uint8).tobytes()
                inode = ctx.view.meta.stat(
                    f"{self.root}/{step_no}/{ref.ckpt_shard_file(li)}")
                if inode.length != len(gold):
                    replicas_wrong += want_replicas
                cs = inode.layout.chunk_size
                idx = int(rng.integers(max(1, -(-len(gold) // cs))))
                chain_id = inode.layout.chain_of_chunk(idx)
                chain = routing.chains[chain_id]
                if len(chain.targets) != want_replicas:
                    replicas_wrong += want_replicas
                for t in chain.targets:
                    got = read_target(ctx.view, routing, chain_id,
                                      ChunkId(inode.id, idx), t.target_id)
                    replicas_seen += 1
                    replicas_wrong += got != gold[idx * cs:(idx + 1) * cs]
        ctx.say(f"[verify] {replicas_seen} replica reads of sampled chunks")
        checks.append(Check("replicas_wrong", replicas_wrong, 0))
        checks.append(Check("no_cycle_verified", 0 if last_ok else 1, 0))
        return checks

    def close(self) -> None:
        for s in self.storages:
            s.close()
