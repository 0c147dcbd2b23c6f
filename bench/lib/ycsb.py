"""YCSB core-workload generator (Cooper et al., SoCC 2010), kept with
the benchmark so that no change to the program can move the traffic.

A copy of the store's own generator (``repro.data.ycsb.Workload``) with
one difference: the scrambled-zipfian permutation is built with a
vectorised splitmix64, bit-identical to the per-key loop, so building
it at 2^22 keys takes a fraction of a second instead of many.  For the
same parameters and seed the op stream equals the original's exactly
(``bench/tests/test_bench_ycsb.py``).

Key popularity follows the bounded zipfian p(rank) ~ 1 / rank^s,
sampled by inverse CDF; ``scramble`` spreads popular ranks over the key
space (YCSB's scrambled zipfian).  ``distribution="latest"`` makes rank
0 the most recently inserted key (YCSB-D), with no scramble.
"""

from __future__ import annotations

import numpy as np

#: (read, update, insert) shares of the YCSB core workloads
MIXES = {
    "read_only": (1.0, 0.0, 0.0),            # YCSB C
    "read_mostly_update": (0.95, 0.05, 0.0),  # YCSB B
    "read_mostly_insert": (0.95, 0.0, 0.05),  # YCSB D
    "write_heavy_update": (0.5, 0.5, 0.0),    # YCSB A
    "write_heavy_insert": (0.5, 0.0, 0.5),
}


def splitmix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finalizer, element-wise on uint64."""
    x = x.astype(np.uint64, copy=True)
    x += np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class Workload:
    """A seeded YCSB op stream over ``num_keys`` loaded keys."""

    def __init__(self, num_keys: int, zipf: float, shares, seed: int,
                 distribution: str = "zipfian", scramble: bool = True):
        if distribution not in ("zipfian", "latest"):
            raise ValueError(f"unknown distribution {distribution!r}")
        shares = tuple(float(s) for s in shares)
        if len(shares) != 3 or min(shares) < 0 or abs(sum(shares) - 1) > 1e-9:
            raise ValueError(f"(read, update, insert) shares must be three "
                             f"non-negative numbers summing to 1: {shares}")
        self.num_keys = num_keys
        self.shares = shares
        self.distribution = distribution
        ranks = np.arange(1, num_keys + 1, dtype=np.float64)
        w = ranks ** (-zipf)
        self._cdf = np.cumsum(w) / w.sum()
        self._rng = np.random.default_rng(seed)
        self._next_insert = num_keys
        self._scramble = None
        if scramble and distribution == "zipfian":
            perm = (splitmix64(np.arange(num_keys, dtype=np.uint64))
                    % np.uint64(1 << 62)).astype(np.int64)
            self._scramble = np.argsort(perm)

    def _sample_keys(self, n: int) -> np.ndarray:
        ranks = np.searchsorted(self._cdf, self._rng.random(n))
        if self.distribution == "latest":
            return np.maximum(self._next_insert - 1 - ranks, 0)
        if self._scramble is not None:
            ranks = self._scramble[ranks]
        return ranks

    def ops_arrays(self, n: int):
        """``n`` ops as (kinds, keys): kind 0 is a read, 1 a write
        (update or insert); inserts take fresh keys in order."""
        kinds3 = self._rng.choice(3, size=n, p=list(self.shares))
        keys = self._sample_keys(n).astype(np.int64)
        is_ins = kinds3 == 2
        n_ins = int(is_ins.sum())
        if n_ins:
            keys[is_ins] = np.arange(self._next_insert,
                                     self._next_insert + n_ins)
            self._next_insert += n_ins
        return (kinds3 != 0).astype(np.uint8), keys
