"""The control of the erasure-coded cells: the redundancy the configuration
states is not there. Every stripe is stored with parity shards of zeros
(CRCs to match, so the servers accept them): all 16 shards are acknowledged,
every clean read is exact, and four lost shards would be unrecoverable."""

import numpy as np


def plant(ctx) -> None:
    from tpu3fs.ops.crc32c import crc32c
    from tpu3fs.ops.stripe import StripeCodec

    inner = StripeCodec.encode_batch

    def encode_batch(self, data):
        shards, crcs = inner(self, data)
        shards = np.array(shards)
        crcs = np.array(crcs)
        shards[:, self.k:] = 0
        crcs[:, self.k:] = crc32c(bytes(self.shard_size))
        return shards, crcs

    StripeCodec.encode_batch = encode_batch
