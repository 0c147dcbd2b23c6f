"""Property tests for the batched KVS *write* plane (PR 2 tentpole).

The staged write plane must be decision-for-decision identical to the
per-op reference path:
  * NumpyCLHT.insert_batch vs sequential inserts: same superseded
    pointers, slot placement and overflow allocation -- including
    duplicate keys, contested buckets and exhausted overflow regions;
  * DPMPool merge_budget/merge_all with the grouped-bucket
    merge_entries_batch vs the per-entry oracle (``vectorized=False``):
    same index state, GC counters, heap invalidations and segment
    cursors under arbitrary budget interleavings, tombstones and
    indirection-table keys;
  * DPMPool.log_write_batch vs per-entry log_write: same pointers,
    segment contents, rotations and backlog order;
  * the merge allowance: a batched flush cannot merge more per epoch
    than the budgeted DPM processors (merge_all -- the synchronous
    protocol merge -- is exempt);
  * DinomoCluster.execute_batch vs per-op read()/write() on mixed
    put/get/update/delete batches for the Dinomo (ArrayDAC), static
    (ArrayStaticCache) and Clover (ArrayCloverCache) planes, including
    mid-batch segment-seal boundaries (rotations + write stalls inside
    one batch) and replicated keys -- swept across the PR 4 merge-plane
    knobs (per-epoch merge allowance in {tiny, inf}, contested-bucket
    density) with a linearizability check over a batched run with
    interleaved stall merges.

The planned merge plane itself (MergeWindowPlan) has its dedicated
adversarial harness in tests/test_mergeplane.py.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DinomoCluster, VARIANTS
from repro.core.clht import NumpyCLHT
from repro.core.dac import ArrayStaticCache, StaticCache
from repro.core.dpm_pool import DPMPool
from repro.data import Workload

VARIANT_NAMES = ["dinomo", "dinomo-s", "clover"]
MIX_NAMES = ["read_mostly_update", "write_heavy_update",
             "write_heavy_insert"]


# ---------------------------------------------------------------------------
# NumpyCLHT.insert_batch vs the scalar insert sequence
# ---------------------------------------------------------------------------
class TestInsertBatchEquivalence:
    @given(st.integers(0, 10**6), st.integers(2, 8), st.integers(1, 150))
    @settings(max_examples=25, deadline=None)
    def test_matches_sequential(self, seed, nb_pow, n):
        """Tiny tables force contested buckets, chains and overflow
        exhaustion; every entry's (old, ok) and the full table state
        must match the scalar sequence."""
        rng = np.random.default_rng(seed)
        a, b = NumpyCLHT(1 << nb_pow), NumpyCLHT(1 << nb_pow)
        for k in rng.integers(0, 120, int(rng.integers(0, 50))):
            a.insert(int(k), int(k) + 500)
            b.insert(int(k), int(k) + 500)
        keys = rng.integers(0, 120, n).astype(np.int64)
        ptrs = rng.integers(0, 10**6, n).astype(np.int64)
        olds, oks = [], []
        for k, p in zip(keys, ptrs):
            o, okk = a.insert(int(k), int(p))
            olds.append(-1 if o is None else o)
            oks.append(okk)
        ob, okb, _grown = b.insert_batch(keys, ptrs)
        assert olds == ob.tolist()
        assert oks == okb.tolist()
        assert np.array_equal(a.keys, b.keys)
        assert np.array_equal(a.ptrs, b.ptrs)
        assert np.array_equal(a.nxt, b.nxt)
        assert (a.overflow_head, a.size, a.version) == \
               (b.overflow_head, b.size, b.version)


# ---------------------------------------------------------------------------
# DPMPool: vectorized merge plane vs the per-entry oracle
# ---------------------------------------------------------------------------
def pool_pair(nb, cap, seed):
    a = DPMPool(num_buckets=nb, segment_capacity=cap, vectorized=False)
    b = DPMPool(num_buckets=nb, segment_capacity=cap, vectorized=True)
    for p in (a, b):
        p.register_kn("kn1")
        p.register_kn("kn2")
        p.bulk_load((k, f"v{k}", 64) for k in range(60))
        p.install_indirect(3)
        p.install_indirect(11)
    return a, b


def pool_state(p):
    segs = {kn: [(s.entries, s.sealed, s.valid, s.merged_upto)
                 for s in ss] for kn, ss in p.segments.items()}
    return (p.heap_val, p.heap_len, segs,
            [(s.kn, s.merged_upto) for s, _ in p.merge_backlog],
            (p.gc.segments_created, p.gc.segments_collected,
             p.gc.entries_merged),
            p.index.size, p.index.version, p.indirect)


class TestMergeBatchEquivalence:
    @given(st.integers(0, 10**6), st.integers(3, 40), st.integers(20, 250))
    @settings(max_examples=15, deadline=None)
    def test_budget_interleavings(self, seed, cap, n_ops):
        """Random writes (updates, tombstones, indirect keys) merged
        under random budgets: full pool state matches the per-entry
        oracle at every merge boundary."""
        rng = np.random.default_rng(seed)
        a, b = pool_pair(1 << 7, cap, seed)
        for i in range(n_ops):
            kn = "kn1" if rng.random() < 0.6 else "kn2"
            k = int(rng.integers(0, 90))
            if rng.random() < 0.12:
                args = (kn, -k - 1, None, 0)
            else:
                args = (kn, k, f"w{i}", 64)
            a.log_write(*args)
            b.log_write(*args)
            if rng.random() < 0.15:
                budget = int(rng.integers(1, 2 * cap))
                assert a.merge_budget(budget) == b.merge_budget(budget)
        assert a.merge_all("kn1") == b.merge_all("kn1")
        assert a.merge_all() == b.merge_all()
        assert np.array_equal(a.index.keys, b.index.keys)
        assert np.array_equal(a.index.ptrs, b.index.ptrs)
        assert pool_state(a) == pool_state(b)

    @given(st.integers(0, 10**6))
    @settings(max_examples=10, deadline=None)
    def test_log_write_batch(self, seed):
        """One log_write_batch call == per-entry log_write: pointers,
        segment fills, rotations and backlog order."""
        rng = np.random.default_rng(seed)
        cap = int(rng.integers(3, 30))
        a = DPMPool(num_buckets=64, segment_capacity=cap, vectorized=False)
        b = DPMPool(num_buckets=64, segment_capacity=cap)
        a.register_kn("kn1")
        b.register_kn("kn1")
        n = int(rng.integers(1, 90))
        keys = rng.integers(0, 50, n).tolist()
        vals = [f"v{i}" for i in range(n)]
        lens = [64] * n
        pa = [a.log_write("kn1", k, v, ln)[0]
              for k, v, ln in zip(keys, vals, lens)]
        pb, _rot = b.log_write_batch("kn1", keys, vals, lens)
        assert pa == pb
        assert a.heap_val == b.heap_val
        assert pool_state(a) == pool_state(b)

    def test_merge_allowance_clamps_budget(self):
        """Satellite regression: with a per-epoch allowance set, no
        sequence of merge_budget calls (the stall path a batched flush
        replays) can merge more than the allowance; merge_all (the
        synchronous reconfiguration merge) is exempt."""
        pool = DPMPool(num_buckets=1 << 8, segment_capacity=16)
        pool.register_kn("kn1")
        for i in range(200):
            pool.log_write("kn1", i, f"v{i}", 64)
        pool.merge_allowance = 40
        done = pool.merge_budget(1000)
        assert done <= 40
        assert pool.merge_budget(1000) + done <= 40
        assert pool.merge_allowance == 40 - done - (40 - done)
        assert pool.merge_budget(16) == 0       # allowance exhausted
        # the synchronous protocol merge still completes everything
        assert pool.merge_all() > 0
        for segs in pool.segments.values():
            for s in segs:
                assert s.merged_upto == len(s.entries)

    def test_merge_allowance_batch_flush_equivalence(self):
        """A budget-capped epoch behaves identically on the per-op and
        batched planes: stalls fire, but neither plane merges past the
        allowance mid-batch."""
        clusters = []
        for reference in (True, False):
            c = DinomoCluster(VARIANTS["dinomo"], num_kns=2,
                              cache_bytes=1 << 18, value_bytes=1024,
                              num_buckets=1 << 12, segment_capacity=32,
                              seed=1, reference_cache=reference)
            c.load(((k, f"v{k}") for k in range(1500)), warm=True)
            c.pool.merge_allowance = 64
            clusters.append(c)
        a, b = clusters
        w1 = Workload(num_keys=1500, zipf=0.8, mix="write_heavy_update",
                      seed=5)
        w2 = Workload(num_keys=1500, zipf=0.8, mix="write_heavy_update",
                      seed=5)
        merged0 = (a.pool.gc.entries_merged, b.pool.gc.entries_merged)
        for i, (kind, key) in enumerate(w1.ops(1200)):
            if kind == "read":
                a.read(key)
            else:
                a.write(key, f"w{i}")
        kinds, keys = w2.ops_arrays(1200)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.pool.gc.entries_merged - merged0[0] <= 64
        assert b.pool.gc.entries_merged - merged0[1] <= 64
        assert a.pool.merge_allowance == b.pool.merge_allowance
        assert sum(kn.stats.write_stalls for kn in b.kns.values()) > 0


# ---------------------------------------------------------------------------
# ArrayStaticCache vs the StaticCache oracle
# ---------------------------------------------------------------------------
class TestArrayStaticCacheEquivalence:
    @given(st.integers(0, 10**6), st.integers(8, 15),
           st.sampled_from([0.0, 0.3, 0.7, 1.0]))
    @settings(max_examples=12, deadline=None)
    def test_decision_for_decision(self, seed, cap_pow, frac):
        rng = np.random.default_rng(seed)
        cap = 1 << cap_pow
        a, b = StaticCache(cap, frac), ArrayStaticCache(cap, frac)
        for i in range(1200):
            r = rng.random()
            k = int(rng.zipf(1.3)) % 300
            ln = int(rng.choice([64, 100, 256]))
            if r < 0.55:
                ra, rb = a.lookup(k), b.lookup(k)
                assert ra == rb
                if ra is None:
                    a.fill_after_miss(k, i, ln)
                    b.fill_after_miss(k, i, ln)
            elif r < 0.8:
                a.fill_after_write(k, i, ln, segment_cached=True)
                b.fill_after_write(k, i, ln, segment_cached=True)
            elif r < 0.9:
                a.invalidate(k)
                b.invalidate(k)
            else:
                a.demote_to_shortcut(k)
                b.demote_to_shortcut(k)
            sa, sb = a.stats, b.stats
            assert (sa.value_hits, sa.shortcut_hits, sa.misses,
                    sa.evictions) == (sb.value_hits, sb.shortcut_hits,
                                      sb.misses, sb.evictions)
            assert (a.value_used, a.shortcut_used) == \
                   (b.value_used, b.shortcut_used)
        for k in range(300):
            assert (k in a.values) == (b.kind[k] == 2)
            assert (k in a.shortcuts) == (b.kind[k] == 1)


# ---------------------------------------------------------------------------
# batched cluster write plane vs the per-op reference path
# ---------------------------------------------------------------------------
def build_pair(variant, seed, cache_bytes, num_keys=4000, num_kns=4,
               segment_capacity=64, num_buckets=1 << 12,
               merge_allowance=None):
    out = []
    for reference in (True, False):
        c = DinomoCluster(VARIANTS[variant], num_kns=num_kns,
                          cache_bytes=cache_bytes, value_bytes=1024,
                          num_buckets=num_buckets,
                          segment_capacity=segment_capacity,
                          seed=seed, reference_cache=reference)
        c.load(((k, f"v{k}") for k in range(num_keys)), warm=True)
        c.pool.merge_allowance = merge_allowance
        out.append(c)
    return out


def cluster_snapshot(c):
    out = {}
    for n, kn in sorted(c.kns.items()):
        cs = kn.cache.stats
        out[n] = (kn.stats.ops, kn.stats.rts, kn.stats.reads,
                  kn.stats.writes, kn.stats.write_stalls,
                  kn.stats.refused,
                  cs.value_hits, cs.shortcut_hits, cs.misses,
                  cs.promotions, cs.demotions, cs.evictions,
                  len(kn.segcache))
    out["gc"] = (c.pool.gc.segments_created,
                 c.pool.gc.segments_collected,
                 c.pool.gc.entries_merged)
    out["ms"] = c.ms_ops
    out["seq"] = c._seq
    return out


def mixed_ops(seed, num_keys, n, mix, delete_frac=0.1):
    """(kinds, keys) arrays with kind 2 (delete) mixed into the writes."""
    w = Workload(num_keys=num_keys, zipf=1.2, mix=mix, seed=seed)
    kinds, keys = w.ops_arrays(n)
    rng = np.random.default_rng(seed + 7)
    kinds = kinds.copy()
    kinds[(kinds == 1) & (rng.random(n) < delete_frac)] = 2
    return kinds, keys


def apply_scalar(c, kinds, keys):
    for i, (kd, k) in enumerate(zip(kinds, keys)):
        if kd == 0:
            c.read(int(k))
        elif kd == 2:
            c.write(int(k), None, delete=True)
        else:
            c.write(int(k), f"w{i}")


class TestWritePlaneEquivalence:
    @given(st.integers(0, 10**6), st.sampled_from(VARIANT_NAMES),
           st.sampled_from(MIX_NAMES), st.integers(15, 20),
           st.sampled_from([None, 24]),          # merge allowance: inf/tiny
           st.sampled_from([1 << 12, 1 << 7]))   # contested-bucket density
    @settings(max_examples=18, deadline=None)
    def test_mixed_batches_identical(self, seed, variant, mix, cache_pow,
                                     allowance, num_buckets):
        """Mixed put/get/update/delete batches across the merge-plane
        knob grid (per-epoch allowance in {tiny, inf}, contested-bucket
        density via the index size): per-KN and per-cache statistics
        identical across all three cache planes.  The clover plane pins
        the uncontested density: its staged per-write merge overlay
        assumes index inserts succeed, so a saturated index (overflow
        region exhausted) is outside its documented contract."""
        if variant == "clover":
            num_buckets = 1 << 12
        a, b = build_pair(variant, seed % 5, 1 << cache_pow,
                          num_buckets=num_buckets,
                          merge_allowance=allowance)
        kinds, keys = mixed_ops(seed, 4000, 3000, mix)
        apply_scalar(a, kinds, keys)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.aggregate_stats() == b.aggregate_stats()
        # final value-plane equivalence (index + heap agree per key)
        probe = np.random.default_rng(seed).integers(0, 4200, 200)
        va = [a.read(int(k))[0] for k in probe]
        vb, _ = b.batch_read(probe)
        assert va == vb

    @given(st.integers(0, 10**6), st.sampled_from(VARIANT_NAMES))
    @settings(max_examples=8, deadline=None)
    def test_seal_boundaries_mid_batch(self, seed, variant):
        """Tiny segments force several rotations (segment seals) and
        write stalls *inside* one batch; the staged flush must replay
        them at exactly the per-op positions."""
        a, b = build_pair(variant, seed % 3, 1 << 19,
                          segment_capacity=24)
        kinds, keys = mixed_ops(seed, 4000, 2500, "write_heavy_update",
                                delete_frac=0.05)
        apply_scalar(a, kinds, keys)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        if variant != "clover":
            # coverage: the batch really crossed seal boundaries
            assert a.pool.gc.segments_created > len(a.kns)
            assert sum(kn.stats.write_stalls
                       for kn in b.kns.values()) > 0

    @given(st.integers(0, 10**6), st.sampled_from(VARIANT_NAMES))
    @settings(max_examples=6, deadline=None)
    def test_collected_values_identical(self, seed, variant):
        """collect_values returns exactly what per-op reads returned,
        write-interleaved (values written earlier in the same batch
        must be visible at the right positions)."""
        a, b = build_pair(variant, seed % 3, 1 << 18)
        kinds, keys = mixed_ops(seed, 4000, 1500, "write_heavy_update")
        want = []
        for i, (kd, k) in enumerate(zip(kinds, keys)):
            if kd == 0:
                want.append((i, a.read(int(k))[0]))
            elif kd == 2:
                a.write(int(k), None, delete=True)
            else:
                a.write(int(k), f"w{i}")
        res = b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                              collect_values=True)
        got = [(i, res.values[i]) for i, _ in want]
        assert got == want

    def test_replicated_keys_in_write_batches(self):
        """Replicated keys synchronize on the shared indirection slot:
        CAS publication, cache pointer updates and stats must match the
        per-op path when rep ops interleave with the staged flush."""
        a, b = build_pair("dinomo", 2, 1 << 19)
        w = Workload(num_keys=4000, zipf=1.6, mix="write_heavy_update",
                     seed=2)
        hot = w.hot_keys(4)
        for c in (a, b):
            for k in hot:
                c.replicate_key(k, 3)
        w1 = Workload(num_keys=4000, zipf=1.6, mix="write_heavy_update",
                      seed=9)
        w2 = Workload(num_keys=4000, zipf=1.6, mix="write_heavy_update",
                      seed=9)
        apply_scalar(a, *w1.ops_arrays(2500))
        kinds, keys = w2.ops_arrays(2500)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.pool.indirect == b.pool.indirect
        # coverage: the batch actually exercised replicated ops
        assert np.isin(keys, np.array(hot)).any()

    def test_linearizable_batched_with_stall_merges(self):
        """Linearizability over a batched put/get/update run with
        interleaved stall merges (tiny segments force rotations + stall
        merges inside the batch, all routed through the planned merge
        plane): collected read results must admit a legal sequential
        order per key.  Deletes are excluded: tombstone visibility is
        merge-deferred by design (the KN drops its soft state but the
        index keeps the key until the DPM processor merges the
        tombstone), identically on both planes."""
        from repro.core.linearizability import Op, check_history
        c = DinomoCluster(VARIANTS["dinomo"], num_kns=4,
                          cache_bytes=1 << 19, value_bytes=1024,
                          num_buckets=1 << 12, segment_capacity=24,
                          seed=3)
        c.load(((k, f"v{k}") for k in range(2000)), warm=True)
        kinds, keys = mixed_ops(11, 2000, 1500, "write_heavy_update",
                                delete_frac=0.0)
        res = c.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                              collect_values=True)
        assert sum(kn.stats.write_stalls
                   for kn in c.kns.values()) > 0    # merges interleaved
        ops = []
        for i, (kd, k) in enumerate(zip(kinds.tolist(), keys.tolist())):
            t = float(i)
            if kd == 0:
                ops.append(Op("read", k, res.values[i], t, t + 0.5))
            else:
                ops.append(Op("write", k, f"w{i}", t, t + 0.5))
        verdicts = check_history(
            ops, initial=lambda k: f"v{k}" if k < 2000 else None)
        assert verdicts and all(verdicts.values())

    @given(st.integers(0, 10**6))
    @settings(max_examples=5, deadline=None)
    def test_blocked_and_refused(self, seed):
        a, b = build_pair("dinomo", seed % 3, 1 << 19)
        victim = sorted(a.kns)[0]
        blocked = sorted(a.kns)[1]
        for c in (a, b):
            c.kns[victim].available = False
        kinds, keys = mixed_ops(seed, 4000, 1500, "write_heavy_update")
        for i, (kd, k) in enumerate(zip(kinds, keys)):
            kn = a.route(int(k))
            if kn == blocked:
                continue
            if kd == 0:
                a.read(int(k), kn)
            elif kd == 2:
                a.write(int(k), None, kn, delete=True)
            else:
                a.write(int(k), f"w{i}", kn)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        blocked_kns=[blocked])
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert b.kns[victim].stats.refused > 0


# ---------------------------------------------------------------------------
# PR 3: the planned-transition engine (core.transition)
# ---------------------------------------------------------------------------
from repro.core.dac import CNT_HIST_MAX, ArrayDAC, DAC


class TestPlannedEngine:
    """The plan/apply split must stay decision-for-decision identical
    to the per-op reference path -- and must actually engage (plan, not
    replay) on steady-state windows, otherwise it is dead code."""

    @given(st.integers(0, 10**6), st.sampled_from(VARIANT_NAMES),
           st.sampled_from(MIX_NAMES))
    @settings(max_examples=10, deadline=None)
    def test_planned_windows_identical(self, seed, variant, mix):
        """Bench-shaped batches (one large execute_batch, warm caches):
        the planner covers most ops and the outcome matches the scalar
        oracle exactly."""
        a, b = build_pair(variant, seed % 3, 1 << 19, num_keys=6000,
                          segment_capacity=256)
        kinds, keys = mixed_ops(seed, 6000, 4000, mix, delete_frac=0.05)
        apply_scalar(a, kinds, keys)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        if variant != "clover":
            plan = b.telemetry.counts
            total = plan["plan.planned_ops"] + plan["plan.replayed_ops"]
            assert total > 0
            assert plan["plan.planned_ops"] > 0
            if mix.startswith("write_heavy"):
                # steady-state write windows must plan, not replay
                # (read-mostly windows may route to the bulk-hit path,
                # which is counted as replay)
                assert plan["plan.planned_ops"] > total // 2

    @given(st.integers(0, 10**6))
    @settings(max_examples=6, deadline=None)
    def test_latest_distribution_mixed(self, seed):
        """YCSB-D-like latest-distribution streams (reads chasing the
        insert frontier) through the planned engine."""
        a, b = build_pair("dinomo", seed % 3, 1 << 19, num_keys=5000)
        w1 = Workload(num_keys=5000, zipf=0.99, mix="read_mostly_insert",
                      seed=seed % 97, distribution="latest")
        w2 = Workload(num_keys=5000, zipf=0.99, mix="read_mostly_insert",
                      seed=seed % 97, distribution="latest")
        for i, (kind, key) in enumerate(w1.ops(3000)):
            if kind == "read":
                a.read(key)
            else:
                a.write(key, f"w{i}")
        kinds, keys = w2.ops_arrays(3000)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)

    def test_clover_read_batch_planned(self):
        """Read-only Clover batches take the bulk apply_plan path and
        stay op-for-op identical (stats, ms load, values)."""
        a, b = build_pair("clover", 1, 1 << 19, num_keys=3000)
        w1 = Workload(num_keys=3000, zipf=1.1, mix="read_only", seed=5)
        w2 = Workload(num_keys=3000, zipf=1.1, mix="read_only", seed=5)
        for kind, key in w1.ops(2000):
            a.read(key)
        kinds, keys = w2.ops_arrays(2000)
        res = b.execute_batch(kinds, keys, collect_values=True)
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.ms_ops == b.ms_ops
        # collected values match fresh reads
        for i in range(0, 2000, 97):
            assert res.values[i] == a.pool.heap_val[
                a.pool.index_lookup(int(keys[i]))[0]]


# ---------------------------------------------------------------------------
# ArrayDAC histogram spill: victim counts >= CNT_HIST_MAX force the
# exact-peek fallback in the Eq. 1 victim sum (satellite audit)
# ---------------------------------------------------------------------------
class TestHistogramSpill:
    @staticmethod
    def _spill_pair(cap, n_keys, miss_rts):
        a = DAC(cap, avg_miss_rts_init=miss_rts)
        b = ArrayDAC(cap, avg_miss_rts_init=miss_rts)
        for k in range(n_keys):
            for c in (a, b):
                c.fill_after_miss(k, 1000 + k, 1024)
        return a, b

    @given(st.integers(0, 10**6))
    @settings(max_examples=10, deadline=None)
    def test_spilled_promotions_match_reference(self, seed):
        """Drive every live shortcut's count past CNT_HIST_MAX (the
        histogram clamp), then exercise Eq. 1 decisions with both
        outcomes: the spill fallback (exact heap peek) must agree with
        the reference DAC decision for decision."""
        rng = np.random.default_rng(seed)
        cap = 4096
        a, b = self._spill_pair(cap, 40, miss_rts=1e5)
        spills = [0]
        orig = b._victim_sum_hist

        def counting(n, exclude_cnt):
            r = orig(n, exclude_cnt)
            if r is None:
                spills[0] += 1
            return r

        b._victim_sum_hist = counting
        keys = [k for k in range(40) if k in b]
        # phase 1: hammer counts far past the histogram bound; the huge
        # avg_miss_rts denies every promotion through the exact path
        for _ in range(CNT_HIST_MAX + 20):
            for k in keys:
                ra, rb = a.lookup(k), b.lookup(k)
                assert ra == rb
        assert max(int(b.count[k]) for k in keys) >= CNT_HIST_MAX
        # phase 2: cheap misses flip the exact decision to promote
        a.avg_miss_rts = b.avg_miss_rts = 1e-4
        order = rng.permutation(keys)
        for k in order:
            ra, rb = a.lookup(int(k)), b.lookup(int(k))
            assert ra == rb
        sa, sb = a.stats, b.stats
        assert (sa.value_hits, sa.shortcut_hits, sa.misses,
                sa.promotions, sa.demotions, sa.evictions) == \
               (sb.value_hits, sb.shortcut_hits, sb.misses,
                sb.promotions, sb.demotions, sb.evictions)
        assert a.used == b.used
        assert spills[0] > 0, "spill fallback never engaged"
        assert sb.promotions > 0, "no promotion decided via the peek"
        for k in range(40):
            assert (k in a.values) == (b.kind[k] == 2)
            assert (k in a.shortcuts) == (b.kind[k] == 1)

    @given(st.integers(0, 10**6))
    @settings(max_examples=5, deadline=None)
    def test_spill_through_batched_engine(self, seed):
        """High-skew mixed batches on tiny caches drive hot shortcut
        counts past the histogram bound inside execute_batch; the
        planned engine (which replays exact-Eq. 1 windows) must stay
        identical to the scalar oracle."""
        a, b = build_pair("dinomo", seed % 3, 1 << 14, num_keys=2000,
                          num_kns=2)
        w1 = Workload(num_keys=2000, zipf=2.0, mix="write_heavy_update",
                      seed=seed % 11)
        w2 = Workload(num_keys=2000, zipf=2.0, mix="write_heavy_update",
                      seed=seed % 11)
        for i, (kind, key) in enumerate(w1.ops(2500)):
            if kind == "read":
                a.read(key)
            else:
                a.write(key, f"w{i}")
        kinds, keys = w2.ops_arrays(2500)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)


# ---------------------------------------------------------------------------
# compiled batch executor (engine="jit") through the write plane
# ---------------------------------------------------------------------------
def build_jit_pair(seed, cache_bytes, num_keys=4000, segment_capacity=64,
                   num_buckets=1 << 12, merge_allowance=None):
    """Two identical array-cache clusters: host engine vs compiled."""
    out = []
    for _ in range(2):
        c = DinomoCluster(VARIANTS["dinomo"], num_kns=4,
                          cache_bytes=cache_bytes, value_bytes=1024,
                          num_buckets=num_buckets,
                          segment_capacity=segment_capacity,
                          seed=seed, reference_cache=False)
        c.load(((k, f"v{k}") for k in range(num_keys)), warm=True)
        c.pool.merge_allowance = merge_allowance
        out.append(c)
    return out


class TestJitWritePlane:
    """The compiled executor across the write-plane knob grid (same
    grid as TestWritePlaneEquivalence): deletes force per-op host
    handoffs inside device windows, tiny allowances and contested
    indexes force mid-batch merges that dirty device-resident state."""

    @given(st.integers(0, 10**6), st.sampled_from(MIX_NAMES),
           st.integers(15, 20),
           st.sampled_from([None, 24]),          # merge allowance
           st.sampled_from([1 << 12, 1 << 7]))   # index contestedness
    @settings(max_examples=10, deadline=None)
    def test_mixed_batches_identical(self, seed, mix, cache_pow,
                                     allowance, num_buckets):
        a, b = build_jit_pair(seed % 5, 1 << cache_pow,
                              num_buckets=num_buckets,
                              merge_allowance=allowance)
        kinds, keys = mixed_ops(seed, 4000, 3000, mix)
        a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert a.aggregate_stats() == b.aggregate_stats()
        probe = np.random.default_rng(seed).integers(0, 4200, 200)
        va, _ = a.batch_read(probe)
        vb, _ = b.batch_read(probe)
        assert va == vb

    @given(st.integers(0, 10**6))
    @settings(max_examples=6, deadline=None)
    def test_seal_boundaries_mid_batch(self, seed):
        """Tiny segments: rotations + stall merges land mid-window and
        invalidate device-side prefetches (the dirty-key/bucket seam);
        the compiled run must replay them at exact per-op positions."""
        a, b = build_jit_pair(seed % 3, 1 << 19, segment_capacity=24)
        kinds, keys = mixed_ops(seed, 4000, 2500, "write_heavy_update",
                                delete_frac=0.05)
        a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert sum(kn.stats.write_stalls for kn in b.kns.values()) > 0

    def test_linearizable_jit_with_stall_merges(self):
        """Linearizability over a jit-batched put/get/update run with
        interleaved stall merges -- the engine="jit" twin of
        test_linearizable_batched_with_stall_merges."""
        from repro.core.linearizability import Op, check_history
        c = DinomoCluster(VARIANTS["dinomo"], num_kns=4,
                          cache_bytes=1 << 19, value_bytes=1024,
                          num_buckets=1 << 12, segment_capacity=24,
                          seed=3)
        c.load(((k, f"v{k}") for k in range(2000)), warm=True)
        kinds, keys = mixed_ops(11, 2000, 1500, "write_heavy_update",
                                delete_frac=0.0)
        res = c.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                              collect_values=True, engine="jit")
        assert sum(kn.stats.write_stalls
                   for kn in c.kns.values()) > 0    # merges interleaved
        ops = []
        for i, (kd, k) in enumerate(zip(kinds.tolist(), keys.tolist())):
            t = float(i)
            if kd == 0:
                ops.append(Op("read", k, res.values[i], t, t + 0.5))
            else:
                ops.append(Op("write", k, f"w{i}", t, t + 0.5))
        verdicts = check_history(
            ops, initial=lambda k: f"v{k}" if k < 2000 else None)
        assert verdicts and all(verdicts.values())
