"""The control of the data-set cell: the chain acknowledges a write that one
replica does not hold. When the window closes, the last replica of every
chain has lost its copy of the data file's chunks (every answer the loader
gave was exact: only the stored form, read replica by replica, shows it)."""


def plant(ctx) -> None:
    ctx.after_window.append(_drop_tail_replica)


def _drop_tail_replica(ctx, driver) -> None:
    from tpu3fs.storage.types import ChunkId

    driver.loader.close()
    inode = ctx.view.meta.stat(driver.path)
    routing = ctx.cluster.admin.refresh_routing()
    cs = inode.layout.chunk_size
    for idx in range(-(-inode.length // cs)):
        chain = routing.chains[inode.layout.chain_of_chunk(idx)]
        t = chain.targets[-1]
        node = routing.node_of_target(t.target_id)
        ctx.view.send(node.node_id, "remove_chunk",
                      (t.target_id, ChunkId(inode.id, idx)))
