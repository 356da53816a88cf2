"""tpu3fs/dataload — the training-side input pipeline.

The headline consumer the reference was built for (PAPER/SURVEY §0:
"training data loaders" lead the workload list; DeepSeek ships the
companion FFRecord format): random batch reads over huge packed datasets
at full storage bandwidth, through the normal client stack — striped
batched chunk IO, atomic-rename commit, the ``dataload`` QoS class,
monitor recorders — no private storage path.

- ``recordio`` — packed record-file format (fixed header, per-record
  offset index + CRC32C, ``.tmp`` → rename commit) and the packer
- ``dataset``  — multi-file global sample index, seeded Feistel-PRP
  per-epoch shuffle (no materialized permutation), dp sharding over the
  process mesh
- ``loader``   — pipelined batch fetcher: coalesced sorted batch reads,
  CRC verify, bounded-byte prefetch, ``jax.device_put`` hand-off
- ``state``    — the four-integer resumable cursor, composing with ckpt
  save sessions (a restored job resumes mid-epoch exactly)

Driven by ``admin_cli dataload-pack|dataload-inspect`` and
``bin/dataload_pack_main.py``.
"""

from __future__ import annotations

from tpu3fs.dataload.dataset import (
    FeistelPermutation,
    IdentityPermutation,
    PackedDataset,
    dp_info,
)
from tpu3fs.dataload.loader import Batch, DataLoader, LoaderConfig
from tpu3fs.dataload.recordio import (
    RecordFile,
    RecordFileWriter,
    pack_records,
    plan_coalesced,
)
from tpu3fs.dataload.state import DataloadState, StateStore

__all__ = [
    "Batch",
    "DataLoader",
    "DataloadState",
    "FeistelPermutation",
    "IdentityPermutation",
    "LoaderConfig",
    "PackedDataset",
    "RecordFile",
    "RecordFileWriter",
    "StateStore",
    "dp_info",
    "pack_records",
    "plan_coalesced",
]
