"""A byte flipped on one target after the acknowledgement: once the window
has closed, the data shard 0 of the last stripe a write batch acknowledged
gets one byte flipped where its target keeps it, in the storage processes'
files under the run's directory (every copy of a 64-byte run of the shard
found there), as a disk would. The stored CRC32C stays the one the write left."""

import mmap
import os


def plant(ctx) -> None:
    from tpu3fs.client.storage_client import StorageClient

    inner = StorageClient.write_stripes
    last: dict = {}

    def write_stripes(self, chain_id, items, **kw):
        out = inner(self, chain_id, items, **kw)
        if out and out[-1] is not None and out[-1].ok:
            last["stripe"] = (chain_id, items[-1][0])
        return out

    StorageClient.write_stripes = write_stripes
    ctx.after_window.append(lambda ctx, driver: flip(ctx, *last["stripe"]))


def flip(ctx, chain_id, chunk_id) -> None:
    from tpu3fs.storage.craq import ReadReq

    routing = ctx.cluster.admin.refresh_routing()
    target = routing.chains[chain_id].target_of_shard(0).target_id
    node = routing.node_of_target(target).node_id
    shard = ctx.view.send(node, "read_rebuild", ReadReq(
        chain_id, chunk_id, 0, -1, target))
    at = len(shard.data) // 2
    needle = bytes(shard.data[at:at + 64])
    flipped = 0
    for dirpath, _dirs, files in os.walk(ctx.run_dir):
        for name in files:
            path = os.path.join(dirpath, name)
            if (not os.path.isfile(path) or os.path.islink(path)
                    or os.path.getsize(path) < len(needle)):
                continue
            with open(path, "r+b") as f, mmap.mmap(f.fileno(), 0) as mm:
                pos = mm.find(needle)
                while pos >= 0:
                    mm[pos] ^= 0x40
                    flipped += 1
                    pos = mm.find(needle, pos + 1)
    ctx.say(f"FAULT flipped {flipped} byte(s) of chunk {chunk_id} shard 0 "
            f"on target {target}")
