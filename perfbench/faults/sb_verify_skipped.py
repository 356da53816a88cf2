"""The device verify answers without computing: every row that landed in
HBM is said to match the checksum it was read with, whatever it holds."""

import numpy as np


def plant(ctx) -> None:
    from tpu3fs.ops.crc32c import CrcVerifier

    def check(self, rows, expected):
        return np.ones(rows.shape[0], dtype=bool)

    CrcVerifier.check = check
