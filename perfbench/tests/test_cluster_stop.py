"""Cluster.stop() removes from /dev/shm what its own processes left there
and nothing else: another run's or a test's rings beside it stay."""

import glob
import os
import struct
import time
import uuid

from perfbench.lib import cluster as cl


def test_stop_unlinks_only_what_the_run_owns(tmp_path):
    tag = uuid.uuid4().hex[:10]
    other = f"other{tag}"
    # pid 1 is alive and no child of this run: a neighbour's entries
    foreign = {f"tpu3fs-iov-{other}": bytes(64),
               f"tpu3fs-ior-{other}": struct.pack(
                   "<IIQQQQII", 0x3F5B10, 8, 0, 0, 0, 0, 2, 1) + bytes(16),
               f"tpu3fs-hs-1-{other}": b"nonce",
               f"sem.tpu3fs-ior-{other}-sq": bytes(32)}
    cluster = cl.Cluster(str(tmp_path), {"storage_nodes": 0})
    try:
        cluster.spawn("holder", "perfbench.tests.shm_holder", tag)
        for name, body in foreign.items():   # appear AFTER the run began
            with open(os.path.join(cl.SHM_DIR, name), "wb") as f:
                f.write(body)
        deadline = time.time() + 60
        while len(glob.glob(f"{cl.SHM_DIR}/tpu3fs-*{tag}")) < 3 + 3:
            assert time.time() < deadline, "the holder never came up"
            time.sleep(0.1)
        pid = cluster.procs["holder"].pid
        mine = {f"tpu3fs-iov-{tag}", f"tpu3fs-ior-{tag}",
                f"tpu3fs-hs-{pid}-{tag}"}
        assert cl.shm_mapped_by({pid}) == {f"tpu3fs-iov-{tag}"}
        assert cl.shm_owner(f"tpu3fs-ior-{tag}") == pid
        assert cl.shm_owner(f"tpu3fs-hs-{pid}-{tag}") == pid
        assert cl.shm_owner(f"tpu3fs-iov-{tag}") == 0
        left = cluster.stop(grace_s=0.3)
        assert left["sigkilled"] == 1 and left["still_running"] == 0
        assert left["shm_removed"] == 3
        now = cl.shm_entries()
        assert not (mine & now)
        assert set(foreign) <= now
    finally:
        cluster.stop(grace_s=0.1)
        for name in foreign:
            try:
                os.unlink(os.path.join(cl.SHM_DIR, name))
            except OSError:
                pass
