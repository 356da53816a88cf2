"""Device-mesh construction for the storage data plane.

The reference's axes of parallelism (SURVEY.md §0.2) map onto a 2-D
``jax.sharding.Mesh``:

- ``dp``    — striping axis: independent chunk batches spread over chain
              groups (ref: round-robin chunk striping over chains,
              docs/design_notes.md "Location of file chunks").
- ``chain`` — replication/EC axis: one ring position per chain member; CRAQ
              head->tail propagation rides ICI via collective_permute (ref:
              RDMA chain forwarding, src/storage/service/StorageOperator.cc).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (re-exported to the kernels)
from jax.sharding import Mesh


def make_storage_mesh(
    chain_len: int,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names=("dp", "chain"),
) -> Mesh:
    """Mesh of shape (n_devices // chain_len, chain_len)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if chain_len < 1 or n % chain_len != 0:
        raise ValueError(f"{n} devices not divisible into chains of {chain_len}")
    grid = np.array(devices).reshape(n // chain_len, chain_len)
    return Mesh(grid, axis_names)
