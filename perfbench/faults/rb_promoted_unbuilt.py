"""The control of the node-loss cell: what a rebuild that promoted a target
unbuilt leaves behind. Once recovery is done (the driver's own wait), every
shard is removed from ONE of the rebuilt targets, target by target, nothing
of the program patched: every load and read-back stays exact because the
client decodes around the hole, and only the stored form, read target by
target, and the decodes counted after recovery show it."""


def plant(ctx) -> None:
    ctx.after_window.append(_empty_one_rebuilt_target)


def _empty_one_rebuilt_target(ctx, driver) -> None:
    import time

    driver.wait_recovered()
    # the rebuild's coordinator learns of the promotion with its next
    # heartbeat and may run one more (empty) pass on the target until
    # then: what is removed before that, it would install again
    time.sleep(3.0)
    routing = ctx.cluster.admin.refresh_routing()
    _shard, target_id = driver.lost_targets[0]
    node = routing.node_of_target(target_id)
    metas = ctx.view.send(node.node_id, "dump_chunkmeta", target_id)
    for meta in metas:
        ctx.view.send(node.node_id, "remove_chunk",
                      (target_id, meta.chunk_id))
    ctx.say(f"FAULT: {len(metas)} shards removed from rebuilt target "
            f"{target_id} on node {node.node_id}")
