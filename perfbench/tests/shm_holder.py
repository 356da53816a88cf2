"""Helper of test_cluster_stop.py, run as a child of a Cluster: leaves in
/dev/shm what a SIGKILLed storage process leaves (a buffer it has mapped, a
ring stamped with its pid, a handshake nonce named by its pid), ignores
SIGTERM and sleeps."""

import mmap
import os
import signal
import struct
import sys
import time

tag = sys.argv[1]
signal.signal(signal.SIGTERM, signal.SIG_IGN)
with open(f"/dev/shm/tpu3fs-iov-{tag}", "wb") as f:
    f.write(bytes(4096))
fd = os.open(f"/dev/shm/tpu3fs-iov-{tag}", os.O_RDWR)
held = mmap.mmap(fd, 4096)
with open(f"/dev/shm/tpu3fs-ior-{tag}", "wb") as f:
    f.write(struct.pack("<IIQQQQII", 0x3F5B10, 8, 0, 0, 0, 0, 2, os.getpid())
            + bytes(16))
with open(f"/dev/shm/tpu3fs-hs-{os.getpid()}-{tag}", "wb") as f:
    f.write(b"nonce")
time.sleep(600)
