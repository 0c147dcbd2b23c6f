"""Adversarial equivalence harness for the planned merge plane (PR 4).

The tentpole contract: ``MergeWindowPlan`` (core.transition.
plan_merge_window -> NumpyCLHT.apply_merge_plan / DPMPool.
apply_merge_plan) must be decision-for-decision identical to the scalar
``insert`` / ``_merge_entry`` sequence -- same superseded pointers
(within-window duplicate chains included), same slot placement (first
empty along the chain, claims in first-occurrence order), same
version/size/GC evolution -- while *self-truncating* at every entry it
cannot prove: tombstones, buckets whose chains must grow, and the
per-epoch merge allowance.

The generators here are adversarial by construction:
  * tiny tables (4..64 primary buckets) force contested buckets, chain
    walks, overflow allocation and overflow-region exhaustion;
  * high key-duplication forces superseded pointers *within* one plan;
  * dense tombstones force plan truncation + scalar replay interleaving;
  * tiny merge allowances force budget exhaustion mid-plan;
  * tiny segments force mid-batch seals (rotations) between plans.

Coverage is asserted (the ``merge_plan.*`` counts of the pool's
telemetry) so the planned path cannot rot
into dead code behind its scalar replay fallback.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DinomoCluster, VARIANTS
from repro.core.clht import NumpyCLHT
from repro.core.dpm_pool import DPMPool
from repro.core.transition import MIN_MERGE_PLAN_OPS, plan_merge_window
from repro.data import Workload


def table_state(t: NumpyCLHT):
    return (t.keys.copy(), t.ptrs.copy(), t.nxt.copy(),
            t.overflow_head, t.size, t.version)


def assert_tables_equal(a: NumpyCLHT, b: NumpyCLHT):
    assert np.array_equal(a.keys, b.keys)
    assert np.array_equal(a.ptrs, b.ptrs)
    assert np.array_equal(a.nxt, b.nxt)
    assert (a.overflow_head, a.size, a.version) == \
           (b.overflow_head, b.size, b.version)


def adversarial_entries(rng, n, key_space, dup_bias=True):
    """(keys, ptrs) with heavy duplication (within-plan supersession)."""
    if dup_bias and n > 4:
        hot = rng.integers(0, key_space, max(key_space // 4, 1))
        keys = np.where(rng.random(n) < 0.5,
                        hot[rng.integers(0, hot.size, n)],
                        rng.integers(0, key_space, n))
    else:
        keys = rng.integers(0, key_space, n)
    return keys.astype(np.int64), \
        rng.integers(0, 10**6, n).astype(np.int64)


# ---------------------------------------------------------------------------
# plan_merge_window unit contracts
# ---------------------------------------------------------------------------
class TestPlanContract:
    def test_tombstone_truncates(self):
        t = NumpyCLHT(1 << 6)
        keys = np.arange(40, dtype=np.int64)
        keys[17] = -5                      # tombstone mid-window
        ptrs = keys + 100
        plan = plan_merge_window(t, keys, ptrs)
        assert plan is not None and plan.ops == 17

    def test_small_windows_replay(self):
        t = NumpyCLHT(1 << 6)
        n = MIN_MERGE_PLAN_OPS - 1
        keys = np.arange(n, dtype=np.int64)
        assert plan_merge_window(t, keys, keys) is None

    def test_max_ops_clamps_the_plan(self):
        """The per-epoch allowance clamps the plan itself: no entry
        past the budget is covered."""
        t = NumpyCLHT(1 << 6)
        keys = np.arange(64, dtype=np.int64)
        plan = plan_merge_window(t, keys, keys + 1, max_ops=20)
        assert plan is not None and plan.ops == 20

    def test_indirect_entries_filtered(self):
        t = NumpyCLHT(1 << 6)
        keys = np.arange(32, dtype=np.int64)
        ind = np.array([3, 7, 11], dtype=np.int64)
        plan = plan_merge_window(t, keys, keys + 1, indirect_keys=ind)
        assert plan.ops == 32
        assert plan.n_index == 29          # 3 entries skipped
        assert plan.n_new == 29
        assert (plan.old == -1).all()
        assert not np.isin(ind, plan.new_keys).any()

    def test_overflowing_bucket_truncates(self):
        """Fill one bucket's whole chain, then plan a window whose
        first entries update and whose later entry must grow the chain:
        the plan truncates exactly at that entry."""
        t = NumpyCLHT(4, overflow_buckets=64)
        # find keys colliding into one bucket
        ks = [k for k in range(4000) if t._bucket(k) == 0][:40]
        # chain of MAX_CHAIN full buckets: 8 * 3 slots
        for k in ks[:24]:
            t.insert(k, k + 1)
        upd = np.asarray(ks[:10], np.int64)          # in-place updates
        fresh = np.asarray(ks[30:32], np.int64)      # need chain growth
        keys = np.concatenate([upd, fresh, upd])
        ptrs = np.arange(keys.size, dtype=np.int64) + 500
        plan = plan_merge_window(t, keys, ptrs)
        assert plan is not None
        assert plan.ops == 10              # truncated at the first fresh

    def test_within_plan_supersession(self):
        """Duplicate keys inside one plan: per-entry old follows the
        duplicate chain, the final table holds the last ptr."""
        t = NumpyCLHT(1 << 6)
        t.insert(5, 900)
        keys = np.array([5, 1, 5, 2, 5, 3, 6, 6, 7, 8], np.int64)
        ptrs = np.arange(10, dtype=np.int64) + 100
        plan = plan_merge_window(t, keys, ptrs)
        assert plan.ops == 10
        got = plan.old.tolist()
        assert got[0] == 900 and got[2] == 100 and got[4] == 102
        assert got[6] == -1 and got[7] == 106
        # superseded set: pre-window + intermediate, no unchanged ptrs
        assert sorted(plan.inv_ptrs.tolist()) == [100, 102, 106, 900]


# ---------------------------------------------------------------------------
# NumpyCLHT.insert_batch (the planned path) vs the scalar sequence
# ---------------------------------------------------------------------------
class TestPlannedInsertEquivalence:
    @given(st.integers(0, 10**6), st.integers(2, 7), st.integers(1, 200))
    @settings(max_examples=30, deadline=None)
    def test_adversarial_tables(self, seed, nb_pow, n):
        """Contested buckets, chain growth, overflow exhaustion and
        within-batch duplicates: every entry's (old, ok) and the full
        table state must match the scalar sequence."""
        rng = np.random.default_rng(seed)
        a, b = NumpyCLHT(1 << nb_pow), NumpyCLHT(1 << nb_pow)
        for k in rng.integers(0, 150, int(rng.integers(0, 80))):
            a.insert(int(k), int(k) + 500)
            b.insert(int(k), int(k) + 500)
        keys, ptrs = adversarial_entries(rng, n, 150)
        olds, oks = [], []
        for k, p in zip(keys, ptrs):
            o, okk = a.insert(int(k), int(p))
            olds.append(-1 if o is None else o)
            oks.append(okk)
        ob, okb, _grown = b.insert_batch(keys, ptrs)
        assert olds == ob.tolist()
        assert oks == okb.tolist()
        assert_tables_equal(a, b)

    def test_planned_path_engages(self):
        """Coverage: on an uncontested table the whole batch must plan
        (zero replayed entries) -- the planned path is not dead code."""
        t = NumpyCLHT(1 << 12)
        rng = np.random.default_rng(0)
        keys, ptrs = adversarial_entries(rng, 512, 4000)
        t.insert_batch(keys, ptrs)
        counts = t.telemetry.counts
        assert counts["merge_plan.planned_entries"] == 512
        assert counts["merge_plan.replayed_entries"] == 0


# ---------------------------------------------------------------------------
# DPMPool merge plane vs the per-entry oracle (vectorized=False)
# ---------------------------------------------------------------------------
def pool_pair(nb, cap, n_load=60, indirect=(3, 11)):
    a = DPMPool(num_buckets=nb, segment_capacity=cap, vectorized=False)
    b = DPMPool(num_buckets=nb, segment_capacity=cap, vectorized=True)
    for p in (a, b):
        p.register_kn("kn1")
        p.register_kn("kn2")
        p.bulk_load((k, f"v{k}", 64) for k in range(n_load))
        for k in indirect:
            p.install_indirect(k)
    return a, b


def pool_state(p):
    segs = {kn: [(s.entries, s.sealed, s.valid, s.merged_upto)
                 for s in ss] for kn, ss in p.segments.items()}
    return (p.heap_val, p.heap_len, segs,
            [(s.kn, s.merged_upto) for s, _ in p.merge_backlog],
            (p.gc.segments_created, p.gc.segments_collected,
             p.gc.entries_merged),
            p.index.size, p.index.version, p.indirect,
            p.merge_allowance)


def drive_pools(a, b, rng, n_ops, *, tombstone_frac, allowance,
                budget_frac, key_space=90):
    """Random write/merge interleavings applied to both pools; merge
    results compared at every boundary. Returns total merged."""
    total = 0
    for i in range(n_ops):
        kn = "kn1" if rng.random() < 0.6 else "kn2"
        k = int(rng.integers(0, key_space))
        if rng.random() < tombstone_frac:
            args = (kn, -k - 1, None, 0)
        else:
            args = (kn, k, f"w{i}", 64)
        a.log_write(*args)
        b.log_write(*args)
        if rng.random() < budget_frac:
            if allowance is not None and rng.random() < 0.4:
                al = int(rng.integers(1, allowance))
                a.merge_allowance = b.merge_allowance = al
            budget = int(rng.integers(1, 3 * a.segment_capacity))
            da, db = a.merge_budget(budget), b.merge_budget(budget)
            assert da == db
            total += da
            a.merge_allowance = b.merge_allowance = None
    return total


class TestPlannedMergeEquivalence:
    @given(st.integers(0, 10**6), st.integers(3, 40))
    @settings(max_examples=20, deadline=None)
    def test_adversarial_interleavings(self, seed, cap):
        """Tombstone-dense writes on a tiny contested table, merged
        under random budgets and mid-plan allowance exhaustion: full
        pool state matches the per-entry oracle at every boundary
        (mid-batch seals included -- cap is tiny, so batches span
        several sealed segments)."""
        rng = np.random.default_rng(seed)
        a, b = pool_pair(1 << 5, cap)
        drive_pools(a, b, rng, int(rng.integers(40, 250)),
                    tombstone_frac=0.15, allowance=2 * cap,
                    budget_frac=0.2)
        assert a.merge_all("kn1") == b.merge_all("kn1")
        assert a.merge_all() == b.merge_all()
        assert_tables_equal(a.index, b.index)
        assert pool_state(a) == pool_state(b)

    @given(st.integers(0, 10**6))
    @settings(max_examples=10, deadline=None)
    def test_overflow_exhaustion(self, seed):
        """A nearly-unindexable keyspace (4 primary buckets, minimal
        overflow region): inserts fail identically on both planes and
        the planned path still matches entry for entry."""
        rng = np.random.default_rng(seed)
        a = DPMPool(num_buckets=4, segment_capacity=16, vectorized=False)
        b = DPMPool(num_buckets=4, segment_capacity=16, vectorized=True)
        for p in (a, b):
            p.register_kn("kn1")
            p.register_kn("kn2")
        drive_pools(a, b, rng, 120, tombstone_frac=0.05, allowance=None,
                    budget_frac=0.25, key_space=400)
        assert a.merge_all() == b.merge_all()
        assert_tables_equal(a.index, b.index)
        assert pool_state(a) == pool_state(b)

    def test_coverage_on_benign_config(self):
        """Acceptance guard: on a bench-shaped pool (2^17 buckets, 512
        segments) the planned path must cover >= 95% of merged entries."""
        pool = DPMPool(num_buckets=1 << 17, segment_capacity=512)
        pool.register_kn("kn1")
        rng = np.random.default_rng(0)
        keys = (rng.zipf(1.5, 12000) % 100000).astype(np.int64)
        pool.telemetry.reset()
        for i, k in enumerate(keys.tolist()):
            pool.log_write("kn1", k, f"w{i}", 64)
            if i % 997 == 0:
                pool.merge_budget(512)
        pool.merge_all()
        counts = pool.telemetry.counts
        tot = (counts["merge_plan.planned_entries"]
               + counts["merge_plan.replayed_entries"])
        assert tot >= 12000
        assert counts["merge_plan.planned_entries"] / tot >= 0.95

    def test_truncated_plan_never_double_charges(self):
        """Satellite regression (allowance accounting): a window whose
        plan truncates (contested tiny table) and replays scalar inside
        one merge_budget call must debit the epoch allowance exactly
        once per merged entry, identically on both planes."""
        for vec in (False, True):
            pool = DPMPool(num_buckets=4, segment_capacity=32,
                           vectorized=vec)
            pool.register_kn("kn1")
            for i in range(300):
                pool.log_write("kn1", i % 60, f"w{i}", 64)
            pool.merge_allowance = 45
            g0 = pool.gc.entries_merged
            done = pool.merge_budget(10**6)
            assert done == 45
            assert pool.merge_allowance == 0
            assert pool.gc.entries_merged - g0 == done
            # exhausted allowance: nothing more merges this epoch
            assert pool.merge_budget(10**6) == 0
            assert pool.gc.entries_merged - g0 == done

    def test_allowance_exhaustion_mid_plan(self):
        """The allowance clamps the plan itself: with a fresh table (no
        truncation pressure) and allowance < window size, exactly
        ``allowance`` entries merge and the rest stay pending."""
        a, b = pool_pair(1 << 12, 256, n_load=0, indirect=())
        rng = np.random.default_rng(7)
        for i in range(256):
            k = int(rng.integers(0, 4000))
            a.log_write("kn1", k, f"w{i}", 64)
            b.log_write("kn1", k, f"w{i}", 64)
        for p in (a, b):
            p.telemetry.reset()
            p.merge_allowance = 100
        assert a.merge_budget(10**6) == 100
        assert b.merge_budget(10**6) == 100
        assert pool_state(a) == pool_state(b)
        assert_tables_equal(a.index, b.index)
        # the planned plane covered the clamped window in plans alone;
        # the oracle plane never counts
        assert not a.telemetry.counts
        assert b.telemetry.counts["merge_plan.planned_entries"] == 100
        assert b.telemetry.counts["merge_plan.replayed_entries"] == 0


# ---------------------------------------------------------------------------
# cluster level: stall/rotation merges route through the planned plane
# ---------------------------------------------------------------------------
def build_pair(variant, seed, cache_bytes, num_keys=4000, num_kns=4,
               segment_capacity=64, num_buckets=1 << 12):
    out = []
    for reference in (True, False):
        c = DinomoCluster(VARIANTS[variant], num_kns=num_kns,
                          cache_bytes=cache_bytes, value_bytes=1024,
                          num_buckets=num_buckets,
                          segment_capacity=segment_capacity,
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


class TestClusterMergePlane:
    @given(st.integers(0, 10**6))
    @settings(max_examples=8, deadline=None)
    def test_stall_merges_planned(self, seed):
        """Tiny segments force rotations + stall merges inside one
        batch; the batched plane (whose stall merges now run through
        MergeWindowPlan) stays identical to the per-op path, and the
        planned merge path demonstrably engaged."""
        a, b = build_pair("dinomo", seed % 3, 1 << 19,
                          segment_capacity=24)
        w1 = Workload(num_keys=4000, zipf=1.2,
                      mix="write_heavy_update", seed=seed % 101)
        w2 = Workload(num_keys=4000, zipf=1.2,
                      mix="write_heavy_update", seed=seed % 101)
        for c in (a, b):
            c.telemetry.reset()
        for i, (kind, key) in enumerate(w1.ops(2000)):
            if kind == "read":
                a.read(key)
            else:
                a.write(key, f"w{i}")
        # per-op stalls plan too
        assert a.telemetry.counts["merge_plan.planned_entries"] > 0
        kinds, keys = w2.ops_arrays(2000)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert b.telemetry.counts["merge_plan.planned_entries"] > 0
        assert sum(kn.stats.write_stalls for kn in b.kns.values()) > 0

    def test_contested_index_cluster(self):
        """A contested index (2^8 buckets for 600+ keys, so chains grow
        mid-run) under the batched write plane: plan truncation +
        scalar replay inside stall merges must stay decision-identical
        end to end."""
        a, b = build_pair("dinomo", 1, 1 << 19, num_keys=600,
                          segment_capacity=32, num_buckets=1 << 8)
        w1 = Workload(num_keys=600, zipf=1.0,
                      mix="write_heavy_insert", seed=3)
        w2 = Workload(num_keys=600, zipf=1.0,
                      mix="write_heavy_insert", seed=3)
        b.telemetry.reset()
        for i, (kind, key) in enumerate(w1.ops(1500)):
            if kind == "read":
                a.read(key)
            else:
                a.write(key, f"w{i}")
        kinds, keys = w2.ops_arrays(1500)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        # adversarial coverage: both planned and replayed paths engaged
        assert b.telemetry.counts["merge_plan.planned_entries"] > 0
        assert b.telemetry.counts["merge_plan.replayed_entries"] > 0


# ---------------------------------------------------------------------------
# nightly-profile sweep (heavy; --runslow)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestMergePlaneSweepSlow:
    @given(st.integers(0, 10**6), st.integers(2, 8),
           st.integers(8, 400), st.floats(0.0, 0.3))
    @settings(max_examples=150, deadline=None)
    def test_insert_batch_deep_sweep(self, seed, nb_pow, n, dup):
        rng = np.random.default_rng(seed)
        a, b = NumpyCLHT(1 << nb_pow), NumpyCLHT(1 << nb_pow)
        pre = rng.integers(0, 300, int(rng.integers(0, 120)))
        for k in pre:
            a.insert(int(k), int(k) + 500)
            b.insert(int(k), int(k) + 500)
        keys, ptrs = adversarial_entries(rng, n, 300)
        olds, oks = [], []
        for k, p in zip(keys, ptrs):
            o, okk = a.insert(int(k), int(p))
            olds.append(-1 if o is None else o)
            oks.append(okk)
        ob, okb, _ = b.insert_batch(keys, ptrs)
        assert olds == ob.tolist() and oks == okb.tolist()
        assert_tables_equal(a, b)

    @given(st.integers(0, 10**6), st.integers(3, 64),
           st.floats(0.0, 0.35))
    @settings(max_examples=60, deadline=None)
    def test_pool_deep_sweep(self, seed, cap, tomb):
        rng = np.random.default_rng(seed)
        a, b = pool_pair(1 << int(rng.integers(4, 8)), cap)
        drive_pools(a, b, rng, int(rng.integers(100, 500)),
                    tombstone_frac=tomb, allowance=3 * cap,
                    budget_frac=0.25,
                    key_space=int(rng.integers(40, 400)))
        assert a.merge_all() == b.merge_all()
        assert_tables_equal(a.index, b.index)
        assert pool_state(a) == pool_state(b)


class TestJitClusterMergePlane:
    """The adversarial cluster merge cases through the compiled batch
    executor: stall merges dirty keys/buckets mid-batch, so the device
    engine must invalidate its prefetches and stay identical to the
    host engine's decisions."""

    @given(st.integers(0, 10**6))
    @settings(max_examples=5, deadline=None)
    def test_stall_merges_jit_identical(self, seed):
        a, b = build_pair("dinomo", seed % 3, 1 << 19,
                          segment_capacity=24)[1], \
               build_pair("dinomo", seed % 3, 1 << 19,
                          segment_capacity=24)[1]
        w = Workload(num_keys=4000, zipf=1.2,
                     mix="write_heavy_update", seed=seed % 101)
        kinds, keys = w.ops_arrays(2000)
        for c in (a, b):
            c.telemetry.reset()
        a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        planned_host = a.telemetry.counts["merge_plan.planned_entries"]
        assert planned_host > 0
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert b.telemetry.counts["merge_plan.planned_entries"] \
            == planned_host
        assert sum(kn.stats.write_stalls for kn in b.kns.values()) > 0

    def test_contested_index_jit(self):
        """Chain growth mid-run (2^8 buckets): merge-plan truncation +
        scalar replay inside stall merges, under the jit engine."""
        a = build_pair("dinomo", 1, 1 << 19, num_keys=600,
                       segment_capacity=32, num_buckets=1 << 8)[1]
        b = build_pair("dinomo", 1, 1 << 19, num_keys=600,
                       segment_capacity=32, num_buckets=1 << 8)[1]
        w1 = Workload(num_keys=600, zipf=1.0,
                      mix="write_heavy_insert", seed=3)
        w2 = Workload(num_keys=600, zipf=1.0,
                      mix="write_heavy_insert", seed=3)
        b.telemetry.reset()
        kinds, keys = w1.ops_arrays(1500)
        a.execute_batch(kinds, keys, values=lambda i: f"w{i}")
        kinds, keys = w2.ops_arrays(1500)
        b.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
        assert cluster_snapshot(a) == cluster_snapshot(b)
        assert b.telemetry.counts["merge_plan.planned_entries"] > 0
        assert b.telemetry.counts["merge_plan.replayed_entries"] > 0
