"""Property tests for the batched KVS data plane (PR 1 tentpole).

The batched engine must be *decision-for-decision* identical to the
per-op reference path:
  * ArrayDAC (array-backed, batch-capable) vs DAC (the unoptimized
    OrderedDict/heapq oracle): same hits, promotions, demotions,
    evictions, byte accounting -- op for op;
  * DinomoCluster.execute_batch vs per-op read()/write(): same per-KN
    and per-cache statistics (hit ratios, RTs/op, promote/demote/evict
    counts) on random YCSB-style traces;
  * TimedSimulation batched vs scalar stepping: identical traces;
  * vectorized routing / CLHT lookups vs their scalar counterparts.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DinomoCluster, TimedSimulation, VARIANTS
from repro.core.clht import NumpyCLHT
from repro.core.dac import DAC, ArrayDAC
from repro.core.dpm_pool import DPMPool
from repro.core.hashring import HashRing, mix64, mix64_batch
from repro.data import Workload

MIX_NAMES = ["read_only", "read_mostly_update", "read_mostly_insert",
             "write_heavy_update"]


def dac_stats(d):
    s = d.stats
    return (s.value_hits, s.shortcut_hits, s.misses, s.promotions,
            s.demotions, s.evictions)


# ---------------------------------------------------------------------------
# ArrayDAC vs the reference DAC oracle
# ---------------------------------------------------------------------------
class TestArrayDACEquivalence:
    @given(st.integers(0, 10**6), st.integers(6, 16), st.floats(1.1, 2.2))
    @settings(max_examples=12, deadline=None)
    def test_decision_for_decision(self, seed, cap_pow, skew):
        """Random op soup: every lookup result and every cache decision
        matches the oracle, after every single op."""
        rng = np.random.default_rng(seed)
        cap = 1 << cap_pow
        a, b = DAC(cap), ArrayDAC(cap)
        for i in range(1500):
            r = rng.random()
            k = int(rng.zipf(skew)) % 400
            ln = int(rng.choice([64, 100, 256]))
            if r < 0.6:
                ra, rb = a.lookup(k), b.lookup(k)
                assert ra == rb
                if ra is None:
                    a.note_miss_rts(2.0 + (i % 3))
                    b.note_miss_rts(2.0 + (i % 3))
                    a.fill_after_miss(k, i, ln)
                    b.fill_after_miss(k, i, ln)
            elif r < 0.85:
                sc = bool(rng.random() < 0.7)
                a.fill_after_write(k, i, ln, segment_cached=sc)
                b.fill_after_write(k, i, ln, segment_cached=sc)
            elif r < 0.9:
                a.invalidate(k)
                b.invalidate(k)
            elif r < 0.95:
                a.demote_to_shortcut(k)
                b.demote_to_shortcut(k)
            else:
                a.update_pointer(k, i, ln)
                b.update_pointer(k, i, ln)
            assert dac_stats(a) == dac_stats(b)
            assert a.used == b.used
            assert a.num_values == b.num_values
            assert a.num_shortcuts == b.num_shortcuts
            assert a.avg_miss_rts == b.avg_miss_rts
        # final membership + per-entry state identical
        for k in range(400):
            in_a = k in a
            assert in_a == (k in b)
            if k in a.values:
                assert b.kind[k] == ArrayDAC.KIND_VALUE
                assert a.values[k].count == b.count[k]
                assert a.values[k].ptr == b.ptr[k]
            elif k in a.shortcuts:
                assert b.kind[k] == ArrayDAC.KIND_SHORTCUT
                assert a.shortcuts[k].count == b.count[k]
                assert a.shortcuts[k].ptr == b.ptr[k]

    @given(st.integers(0, 10**6))
    @settings(max_examples=8, deadline=None)
    def test_bulk_value_hits_match_per_op(self, seed):
        rng = np.random.default_rng(seed)
        a, b = DAC(1 << 18), ArrayDAC(1 << 18)
        for k in range(64):
            a.fill_after_miss(k, k, 100)
            b.fill_after_miss(k, k, 100)
        run = rng.integers(0, 64, 300).astype(np.int64)
        for k in run:
            a.lookup(int(k))
        b.bulk_value_hits(run)
        assert dac_stats(a) == dac_stats(b)
        for k in range(64):
            assert a.values[k].count == b.count[k]
        # LRU order identical afterwards: force demotions via a large fill
        a.fill_after_miss(999, 1, 1 << 17)
        b.fill_after_miss(999, 1, 1 << 17)
        assert dac_stats(a) == dac_stats(b)
        assert sorted(a.values) == sorted(
            int(k) for k in np.nonzero(b.kind == 2)[0])


# ---------------------------------------------------------------------------
# batched cluster plane vs the per-op reference path
# ---------------------------------------------------------------------------
def build_pair(variant, seed, cache_bytes, num_keys=6000):
    out = []
    for reference in (True, False):
        c = DinomoCluster(VARIANTS[variant], num_kns=4,
                          cache_bytes=cache_bytes, value_bytes=1024,
                          num_buckets=1 << 13, segment_capacity=256,
                          seed=seed, reference_cache=reference)
        c.load(((k, f"v{k}") for k in range(num_keys)), warm=True)
        out.append(c)
    return out


def cluster_snapshot(c):
    out = {}
    for n, kn in sorted(c.kns.items()):
        cs = kn.cache.stats
        out[n] = (kn.stats.ops, kn.stats.rts, kn.stats.reads,
                  kn.stats.writes, kn.stats.write_stalls,
                  cs.value_hits, cs.shortcut_hits, cs.misses,
                  cs.promotions, cs.demotions, cs.evictions,
                  len(kn.segcache))
    out["gc"] = (c.pool.gc.segments_created,
                 c.pool.gc.segments_collected,
                 c.pool.gc.entries_merged)
    return out


class TestBatchedClusterEquivalence:
    @given(st.integers(0, 10**6), st.sampled_from(MIX_NAMES),
           st.floats(0.4, 2.1), st.integers(14, 21))
    @settings(max_examples=10, deadline=None)
    def test_stats_identical(self, seed, mix, zipf, cache_pow):
        """Per-op reference cluster and batched cluster produce the
        same hit ratios, RTs/op and promote/demote/evict counts on the
        same YCSB-style trace (writes included)."""
        a, b = build_pair("dinomo", seed % 7, 1 << cache_pow)
        w1 = Workload(num_keys=6000, zipf=zipf, mix=mix, seed=seed)
        w2 = Workload(num_keys=6000, zipf=zipf, mix=mix, seed=seed)
        ops = w1.ops(4000)
        for i, (kind, key) in enumerate(ops):
            if kind == "read":
                a.read(key)
            else:
                a.write(key, f"w{i}")
        kinds, keys = w2.ops_arrays(4000)
        assert [k for _, k in ops] == keys.tolist()
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.aggregate_stats() == b.aggregate_stats()

    @given(st.integers(0, 10**6))
    @settings(max_examples=5, deadline=None)
    def test_batch_read_values(self, seed):
        a, b = build_pair("dinomo", seed % 5, 1 << 19)
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 6000, 300).astype(np.int64)
        want = [a.read(int(k))[0] for k in keys]
        got, res = b.batch_read(keys)
        assert got == want
        assert res.executed == 300

    def test_merge_cadence_helpers_match(self):
        from benchmarks.common import (execute_ops_batched,
                                       execute_ops_scalar)
        a, b = build_pair("dinomo", 3, 1 << 19)
        w1 = Workload(num_keys=6000, zipf=0.99,
                      mix="write_heavy_update", seed=3)
        w2 = Workload(num_keys=6000, zipf=0.99,
                      mix="write_heavy_update", seed=3)
        wa = execute_ops_scalar(a, w1.ops(3000))
        kinds, keys = w2.ops_arrays(3000)
        wb = execute_ops_batched(b, kinds, keys)
        assert wa == wb
        assert cluster_snapshot(a) == cluster_snapshot(b)

    def test_blocked_and_refused_kns(self):
        a, b = build_pair("dinomo", 1, 1 << 19)
        victim = sorted(a.kns)[0]
        for c in (a, b):
            c.kns[victim].available = False
        w = Workload(num_keys=6000, zipf=0.99, mix="read_only", seed=1)
        kinds, keys = w.ops_arrays(2000)
        for kd, k in zip(kinds, keys):
            a.read(int(k))
        b.execute_batch(kinds, keys)
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.kns[victim].stats.refused == b.kns[victim].stats.refused
        assert b.kns[victim].stats.refused > 0


# ---------------------------------------------------------------------------
# timed simulation: batched stepping == scalar stepping
# ---------------------------------------------------------------------------
class TestTimedSimEquivalence:
    @given(st.integers(0, 10**6), st.sampled_from(["dinomo", "clover"]))
    @settings(max_examples=4, deadline=None)
    def test_trace_identical(self, seed, variant):
        from repro.core import PolicyConfig
        sims = []
        for batched in (False, True):
            c = DinomoCluster(VARIANTS[variant], num_kns=4,
                              cache_bytes=1 << 19, value_bytes=1024,
                              num_buckets=1 << 13, segment_capacity=256,
                              policy=PolicyConfig(grace_period_s=10.0,
                                                  epoch_s=5.0, max_kns=8))
            c.load((k, f"v{k}") for k in range(3000))
            w = Workload(num_keys=3000, zipf=0.99,
                         mix="write_heavy_update", seed=seed % 17)
            sims.append(TimedSimulation(
                c, w.timed_batched if batched else w.timed, dt=1.0,
                sample_ops=1200, batched=batched))
        for sim in sims:
            sim.run(25.0, lambda t: 6e6 if 8 <= t <= 18 else 2e5)
        a, b = sims
        assert len(a.trace) == len(b.trace)
        for pa, pb in zip(a.trace, b.trace):
            assert pa.t == pb.t and pa.num_kns == pb.num_kns
            assert pa.throughput == pytest.approx(pb.throughput)
            assert pa.avg_latency == pytest.approx(pb.avg_latency)
        assert np.array_equal(a._ef_keys, b._ef_keys)
        assert np.array_equal(a._ef_cnts, b._ef_cnts)


# ---------------------------------------------------------------------------
# vectorized routing / index lookups
# ---------------------------------------------------------------------------
class TestVectorizedLookups:
    @given(st.integers(0, 10**6), st.integers(2, 9))
    @settings(max_examples=15, deadline=None)
    def test_ring_owner_batch(self, seed, n_members):
        ring = HashRing([f"kn{i}" for i in range(n_members)], vnodes=32)
        keys = np.random.default_rng(seed).integers(0, 1 << 62, 500)
        ids, names = ring.owner_ids(keys)
        for i, k in enumerate(keys[:100]):
            assert names[ids[i]] == ring.owner(int(k))

    @given(st.integers(0, 10**6))
    @settings(max_examples=20, deadline=None)
    def test_mix64_batch(self, seed):
        ks = np.random.default_rng(seed).integers(0, 1 << 62, 200)
        got = mix64_batch(ks)
        for i in range(0, 200, 7):
            assert int(got[i]) == mix64(int(ks[i]))

    @given(st.integers(0, 10**6), st.integers(6, 10))
    @settings(max_examples=10, deadline=None)
    def test_clht_lookup_batch(self, seed, nb_pow):
        rng = np.random.default_rng(seed)
        t = NumpyCLHT(1 << nb_pow)
        for k in rng.integers(0, 5000, 800):
            t.insert(int(k), int(k) + 7)
        probe = rng.integers(0, 6000, 1000)
        bp, bpr = t.lookup_batch(probe)
        for i in range(0, 1000, 13):
            p, pr = t.lookup(int(probe[i]))
            assert (p if p is not None else -1) == bp[i]
            assert pr == bpr[i]

    def test_pool_batch_lookup_with_indirection(self):
        pool = DPMPool(num_buckets=1 << 10, segment_capacity=64)
        pool.bulk_load((k, f"v{k}", 64) for k in range(800))
        pool.install_indirect(5)
        pool.install_indirect(11)
        bp, bpr = pool.index_lookup_batch(np.arange(1000))
        for k in range(1000):
            p, pr = pool.index_lookup(k)
            assert (p if p is not None else -1) == bp[k]
            assert pr == bpr[k]


# ---------------------------------------------------------------------------
# compiled batch executor (engine="jit") vs the host window engine
# ---------------------------------------------------------------------------
def build_jit_pair(seed, cache_bytes, num_keys=6000):
    """Two identical array-cache clusters: one runs the host window
    engine, the other the compiled batch executor."""
    out = []
    for _ in range(2):
        c = DinomoCluster(VARIANTS["dinomo"], num_kns=4,
                          cache_bytes=cache_bytes, value_bytes=1024,
                          num_buckets=1 << 13, segment_capacity=256,
                          seed=seed, reference_cache=False)
        c.load(((k, f"v{k}") for k in range(num_keys)), warm=True)
        out.append(c)
    return out


class TestJitEngineEquivalence:
    """ISSUE 9 tentpole: ``execute_batch(engine="jit")`` must be
    decision-for-decision identical to the host window engine on the
    same sweep grid the host engine is pinned against the per-op
    reference with -- which transitively pins the compiled executor to
    the scalar path (truncation residuals replay through the host
    engine, so every config exercises the handoff seam)."""

    @given(st.integers(0, 10**6), st.sampled_from(MIX_NAMES),
           st.floats(0.4, 2.1), st.integers(14, 21))
    @settings(max_examples=8, deadline=None)
    def test_stats_identical(self, seed, mix, zipf, cache_pow):
        a, b = build_jit_pair(seed % 7, 1 << cache_pow)
        w = Workload(num_keys=6000, zipf=zipf, mix=mix, seed=seed)
        kinds, keys = w.ops_arrays(4000)
        a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.aggregate_stats() == b.aggregate_stats()

    def test_dispatch_and_replay_both_engage(self):
        """Coverage pin: on a write-heavy trace with a tight cache the
        compiled engine genuinely dispatches device windows AND hands
        truncation residuals to host replay -- the equivalence sweep
        above cannot rot into an always-replay identity."""
        a, b = build_jit_pair(3, 1 << 15)
        w = Workload(num_keys=6000, zipf=1.2, mix="write_heavy_update",
                     seed=3)
        kinds, keys = w.ops_arrays(6000)
        a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
        assert b.telemetry.total("jit.dispatch") > 0
        assert b.telemetry.total("host.replay") > 0
        assert a.telemetry.total("jit.dispatch") == 0
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.aggregate_stats() == b.aggregate_stats()

    def test_collected_values_identical(self):
        a, b = build_jit_pair(5, 1 << 18)
        w = Workload(num_keys=6000, zipf=0.99, mix="read_mostly_update",
                     seed=5)
        kinds, keys = w.ops_arrays(3000)
        ra = a.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                             collect_values=True)
        rb = b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                             collect_values=True, engine="jit")
        assert ra.values == rb.values
        assert cluster_snapshot(a) == cluster_snapshot(b)

    def test_chained_batches_stay_identical(self):
        """Residency across batches: device state is uploaded once and
        synced at batch end; a later batch must see exactly the state
        the host engine would have."""
        a, b = build_jit_pair(7, 1 << 17)
        for s in range(3):
            w = Workload(num_keys=6000, zipf=1.1,
                         mix="write_heavy_update", seed=s)
            kinds, keys = w.ops_arrays(2000)
            a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
            b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                            engine="jit")
            assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.aggregate_stats() == b.aggregate_stats()
