"""The benchmark's copy of the YCSB generator gives the store's own
generator's op stream, op for op."""

import os
import sys

import numpy as np
import pytest

from bench.lib.ycsb import MIXES, Workload, splitmix64

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.core.hashring import mix64  # noqa: E402
from repro.data import ycsb as store_ycsb  # noqa: E402


def test_splitmix64_matches_the_scalar_mixer():
    xs = np.array([0, 1, 2, 12345, (1 << 40) + 7, (1 << 63) - 1],
                  dtype=np.uint64)
    assert splitmix64(xs).tolist() == [mix64(int(x)) for x in xs]


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("zipf,distribution,seed", [
    (0.99, "zipfian", 0), (0.5, "zipfian", 2 ** 33 + 17),
    (0.99, "latest", 5)])
def test_stream_equals_the_store_generator(mix, zipf, distribution, seed):
    keys = 5000
    ours = Workload(keys, zipf, MIXES[mix], seed, distribution=distribution)
    theirs = store_ycsb.Workload(num_keys=keys, zipf=zipf, mix=mix,
                                 seed=seed, distribution=distribution)
    for n in (1000, 777):
        k1, key1 = ours.ops_arrays(n)
        k2, key2 = theirs.ops_arrays(n)
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(key1, key2)


def test_shares_are_validated():
    with pytest.raises(ValueError):
        Workload(100, 0.99, (0.5, 0.6, 0.0), 0)
    with pytest.raises(ValueError):
        Workload(100, 0.99, (1.0, 0.0, 0.0), 0, distribution="uniform")
