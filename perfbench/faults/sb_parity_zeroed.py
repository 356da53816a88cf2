"""The control of the storage-bench cell: the redundancy the configuration
states is not there. kv_parity_zeroed's plant: every stripe is stored with a
parity shard of zeros (CRCs to match, so the servers accept it); all four
shards are acknowledged, every clean read is exact and verifies, and a lost
data shard would be unrecoverable."""

from .kv_parity_zeroed import plant  # noqa: F401
