"""The plain reference: a dictionary with the store's semantics, and the
comparisons that decide ``correct``.

It imports nothing of the store.  A loaded key ``k`` holds ``v{k}``; a
write of the op at global position ``g`` stores ``w{g}``; a read returns
the key's latest write in op order, or nothing for a key never loaded
or written.  Every number compared has the limit 0.
"""

from __future__ import annotations

import numpy as np

#: each compared number and its limit: all are exact counts
LIMITS = {
    "read_mismatch": 0,      # reads whose answer is not the reference's
    "unexecuted": 0,         # ops submitted but not executed
    "readback_mismatch": 0,  # touched keys whose merged value differs
    "integrity": 0,          # pool.verify_integrity() violations
}


def loaded_value(key: int) -> str:
    return f"v{key}"


def write_value(op: int) -> str:
    """The payload of the op at global position ``op``."""
    return f"w{op}"


class ReferenceStore:
    """Sequential key-value semantics over ``num_keys`` loaded keys."""

    def __init__(self, num_keys: int):
        self.num_keys = num_keys
        self.written: dict[int, str] = {}

    def read(self, key: int):
        v = self.written.get(key)
        if v is None and 0 <= key < self.num_keys:
            return loaded_value(key)
        return v

    def replay(self, kinds: np.ndarray, keys: np.ndarray, base: int,
               answers) -> int:
        """Apply one batch in op order (op ``i`` is global op ``base +
        i``) and count the reads whose answer differs from the
        reference's; ``answers`` None means no read was answered."""
        bad = 0
        written = self.written
        for i, (kd, k) in enumerate(zip(kinds.tolist(), keys.tolist())):
            if kd == 0:
                if answers is None or answers[i] != self.read(k):
                    bad += 1
            else:
                written[k] = write_value(base + i)
        return bad

    def readback_mismatch(self, keys: np.ndarray, values) -> int:
        """Keys whose value read back from the store differs."""
        return sum(v != self.read(k)
                   for k, v in zip(keys.tolist(), values))


def verdict(checks: dict) -> bool:
    return all(checks[k] <= LIMITS[k] for k in LIMITS)
