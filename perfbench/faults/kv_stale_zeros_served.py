"""The stale check passes a zero hole on as a block: an entry the collector
took is still reachable through an inode the client holds, its chunks are
gone, and what reads back is handed to the caller as rows (the shape of the
chaos corpus's peer_fill_stale, here on the block store's decode).

So that a run does not hang on the collector's timing, the fault stages the
situation itself: inside the window, the first few batched gets that reach
the store each lose their first entry the way the collector and the meta
server's chunk reclaim take one (the path removed, then the chunks), once
its inode is held."""

import numpy as np

STAGED = 3


def plant(ctx) -> None:
    from tpu3fs.kvcache.blocks import PrefixBlockStore
    from tpu3fs.kvcache.cache import KVCacheClient
    from tpu3fs.kvcache.layout import decode_array, shard_path
    from tpu3fs.utils.result import Code, FsError

    block = ctx.config["block"]

    def _decode(self, key, raw):
        try:
            return decode_array(raw)
        except FsError as e:
            if e.code != Code.KVCACHE_STALE:
                raise
        return np.zeros(tuple(block["shape"]), dtype=block["dtype"])

    PrefixBlockStore._decode = _decode

    inner = KVCacheClient.batch_get
    state = {"n": 0}

    def batch_get(self, keys):
        if ctx.window_open() and keys and state["n"] < STAGED:
            state["n"] += 1
            key = keys[0]
            path = shard_path(self.root, key)
            try:
                inode = self._cached_inode(key) or self._meta.stat(path)
                self._cache_inode(key, inode)
                self._meta.remove(path)
                self._fio.remove_chunks(inode)
            except FsError:
                pass   # already gone: the next get stages another
        return inner(self, keys)

    KVCacheClient.batch_get = batch_get
