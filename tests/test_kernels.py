"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp/numpy oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clht import bucket_of, clht_init, clht_insert, clht_lookup
from repro.core.log import heap_append, heap_init, log_append, segment_init
from repro.kernels.clht_probe import (clht_probe, clht_probe_ref,
                                      kvs_lookup, kvs_lookup_ref,
                                      pack_table)
from repro.kernels.clht_probe.ops import lookup as probe_lookup
from repro.kernels.decode_attention import (merge_partials, normalize,
                                            paged_decode_attention,
                                            paged_decode_ref)
from repro.kernels.flash_attention import (attention, blocked_mha_jnp,
                                           flash_attention, mha_ref)
from repro.kernels.log_merge import (log_append_merge,
                                     log_append_merge_ref, log_merge,
                                     log_merge_ref, merge_segment_fast,
                                     merge_segment_planned,
                                     merge_window_plan_ref)
from repro.kernels.ssd_scan import ssd, ssd_ref, ssd_scan

RNG = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nb,nkeys,dtype", [
    (64, 100, np.int32), (128, 400, np.int32), (256, 50, np.int32)])
def test_clht_probe_sweep(nb, nkeys, dtype):
    keys = RNG.choice(10_000, nkeys, replace=False).astype(dtype)
    t = clht_init(nb)
    t, *_ = clht_insert(t, jnp.array(keys), jnp.arange(nkeys, dtype=jnp.int32))
    lines = pack_table(t.keys, t.ptrs, t.nxt)
    probe = jnp.array(np.concatenate(
        [keys[:nkeys // 2], RNG.integers(10_001, 20_000, 25)]).astype(dtype))
    bids = bucket_of(probe, nb)
    p_k, f_k = clht_probe(lines, bids, probe)
    p_r, f_r = clht_probe_ref(lines, bids, probe)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))


@pytest.mark.parametrize("nb,nkeys,width,block", [
    (64, 100, 8, 128), (256, 500, 4, 64), (64, 600, 4, 128)])
def test_kvs_lookup_fused_matches_ref(nb, nkeys, width, block):
    """Fused probe+gather kernel == chain walk + separate heap gather,
    including keys that overflow into chained buckets and misses."""
    keys = RNG.choice(10_000, nkeys, replace=False).astype(np.int32)
    t = clht_init(nb)
    heap = heap_init(nkeys + 8, width)
    vals = jnp.arange(nkeys * width, dtype=jnp.int32).reshape(nkeys, width)
    heap, ptrs = heap_append(heap, vals)
    t, _, ok, _ = clht_insert(t, jnp.array(keys), ptrs)
    probe = jnp.array(np.concatenate(
        [keys[:nkeys // 2], RNG.integers(10_001, 20_000, 37)])
        .astype(np.int32))
    v1, p1, f1 = kvs_lookup(t, heap, probe, block=block)
    v2, p2, f2 = kvs_lookup_ref(t, heap, probe)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_clht_probe_full_lookup_matches_chain_walk():
    keys = RNG.choice(5000, 600, replace=False).astype(np.int32)
    t = clht_init(64)   # heavy chains
    t, _, ok, _ = clht_insert(t, jnp.array(keys),
                              jnp.arange(600, dtype=jnp.int32))
    probe = jnp.array(keys[np.asarray(ok)[:600]][:200])
    p1, f1 = probe_lookup(t, probe)
    p2, f2, _ = clht_lookup(t, probe)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nb,entries", [(64, 200), (128, 500), (32, 64)])
def test_log_merge_sweep(nb, entries):
    keys = RNG.integers(0, nb * 2, entries).astype(np.int32)
    ptrs = np.arange(entries, dtype=np.int32)
    t = clht_init(nb)
    lines = pack_table(t.keys, t.ptrs, t.nxt)
    bids = np.asarray(bucket_of(jnp.array(keys), nb))
    l_k, o_k, ok_k = log_merge(jnp.array(lines), jnp.array(bids),
                               jnp.array(keys), jnp.array(ptrs))
    l_r, o_r, ok_r = log_merge_ref(np.asarray(lines), bids, keys, ptrs)
    np.testing.assert_array_equal(np.asarray(l_k), l_r)
    np.testing.assert_array_equal(np.asarray(o_k), o_r)
    np.testing.assert_array_equal(np.asarray(ok_k), ok_r)


@pytest.mark.parametrize("nb,entries,space", [
    (64, 200, 6), (128, 500, 3), (32, 64, 2), (16, 300, 4)])
def test_merge_window_plan_ref_matches_sequential(nb, entries, space):
    """The planned-layout oracle (grouped last-wins updates + ranked
    slot claims -- the MergeWindowPlan layout) is decision-for-decision
    identical to the entry-at-a-time log_merge_ref, including duplicate
    chains and full-bucket claim failures."""
    keys = RNG.integers(0, nb * space, entries).astype(np.int32)
    ptrs = RNG.integers(0, 10**6, entries).astype(np.int32)
    t = clht_init(nb)
    lines = np.asarray(pack_table(t.keys, t.ptrs, t.nxt))
    pk = RNG.integers(0, nb * space, 40).astype(np.int32)
    pb = np.asarray(bucket_of(jnp.array(pk), nb))
    lines, _, _ = log_merge_ref(lines, pb, pk, pk + 7000)
    bids = np.asarray(bucket_of(jnp.array(keys), nb))
    l_a, o_a, ok_a = log_merge_ref(lines, bids, keys, ptrs)
    l_b, o_b, ok_b = merge_window_plan_ref(lines, bids, keys, ptrs)
    np.testing.assert_array_equal(l_a, l_b)
    np.testing.assert_array_equal(o_a, o_b)
    np.testing.assert_array_equal(ok_a, ok_b)


@pytest.mark.parametrize("nb,n,space", [(128, 200, 30), (32, 220, 3),
                                        (512, 400, 6)])
def test_merge_segment_planned_matches_fast(nb, n, space):
    """The planned-layout merge (host MergeWindowPlan + bulk device
    scatters, chain-overflow tail falling back to sequential inserts)
    matches merge_segment_fast table-for-table and entry-for-entry."""
    seg = segment_init(max(n + 8, 16))
    keys = RNG.integers(0, nb * space, n).astype(np.int32)
    seg, _ = log_append(seg, jnp.array(keys),
                        jnp.arange(n, dtype=jnp.int32) + 5000)
    t0 = clht_init(nb)
    pre = RNG.integers(0, nb * space, nb).astype(np.int32)
    t0, *_ = clht_insert(t0, jnp.array(pre),
                         jnp.array(pre) + 9000)
    ta, oa, ka = merge_segment_planned(t0, seg)
    tb, ob, kb = merge_segment_fast(t0, seg)
    np.testing.assert_array_equal(np.asarray(ta.keys),
                                  np.asarray(tb.keys))
    np.testing.assert_array_equal(np.asarray(ta.ptrs),
                                  np.asarray(tb.ptrs))
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(ob))
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))


def test_merge_segment_fast_equals_sequential_insert():
    seg = segment_init(256)
    keys = RNG.choice(4000, 200, replace=False).astype(np.int32)
    seg, _ = log_append(seg, jnp.array(keys),
                        jnp.arange(200, dtype=jnp.int32))
    t1, _, ok1 = merge_segment_fast(clht_init(128), seg)
    t2, _, ok2, _ = clht_insert(clht_init(128), seg.keys, seg.ptrs,
                                jnp.arange(256) < 200)
    p1, f1, _ = clht_lookup(t1, jnp.array(keys))
    p2, f2, _ = clht_lookup(t2, jnp.array(keys))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


@pytest.mark.parametrize("nb,cap,width,batches", [
    (64, 96, 8, 3), (128, 64, 4, 2), (32, 48, 4, 3)])
def test_log_append_merge_fused_matches_ref(nb, cap, width, batches):
    """Fused heap-append + log-append + Pallas merge == the un-fused
    jnp path (sequential chain inserts), across successive batches with
    duplicate keys and a final batch that overflows the segment."""
    tk = tr = clht_init(nb)
    sk = sr = segment_init(cap)
    hk = hr = heap_init(2 * cap + 8, width)
    for b in range(batches):
        n = int(RNG.integers(4, cap // batches))
        keys = jnp.array(RNG.integers(0, nb, n).astype(np.int32))
        vals = jnp.array(RNG.integers(0, 99, (n, width)).astype(np.int32))
        tk, sk, hk, pk, ok_, okk = log_append_merge(tk, sk, hk, keys, vals)
        tr, sr, hr, pr, or_, okr = log_append_merge_ref(tr, sr, hr, keys,
                                                        vals)
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
        np.testing.assert_array_equal(np.asarray(ok_), np.asarray(or_))
        np.testing.assert_array_equal(np.asarray(okk), np.asarray(okr))
        np.testing.assert_array_equal(np.asarray(tk.keys),
                                      np.asarray(tr.keys))
        np.testing.assert_array_equal(np.asarray(tk.ptrs),
                                      np.asarray(tr.ptrs))
        np.testing.assert_array_equal(np.asarray(hk.data),
                                      np.asarray(hr.data))
        assert int(sk.merged) == int(sr.merged) == int(sk.count)
    # overflowing batch: state unchanged, ok all-False on both paths
    big = jnp.array(RNG.integers(0, nb, cap).astype(np.int32))
    bv = jnp.zeros((cap, width), jnp.int32)
    tk2, sk2, hk2, _, _, okk2 = log_append_merge(tk, sk, hk, big, bv)
    tr2, _, hr2, _, _, okr2 = log_append_merge_ref(tr, sr, hr, big, bv)
    assert not bool(np.asarray(okk2).any())
    assert not bool(np.asarray(okr2).any())
    np.testing.assert_array_equal(np.asarray(tk2.keys),
                                  np.asarray(tk.keys))
    assert int(hk2.head) == int(hk.head) == int(hr2.head)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kh,sq,sk,d,causal,dtype", [
    (1, 4, 4, 64, 64, 32, True, jnp.float32),
    (2, 8, 2, 128, 128, 64, True, jnp.bfloat16),
    (1, 4, 1, 32, 128, 32, False, jnp.float32),
    (1, 2, 2, 256, 256, 16, True, jnp.float32),
])
def test_flash_attention_sweep(b, h, kh, sq, sk, d, causal, dtype):
    q = randn((b, h, sq, d), dtype)
    k = randn((b, kh, sk, d), dtype)
    v = randn((b, kh, sk, d), dtype)
    o_k = flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    o_r = mha_ref(q, k, v, causal=causal)
    tol = 2.5e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               atol=tol, rtol=tol)


def test_blocked_jnp_equals_dense():
    q = randn((1, 4, 64, 32))
    k = randn((1, 2, 2048, 32))
    v = randn((1, 2, 2048, 32))
    o_b = blocked_mha_jnp(q, k, v, causal=False, bk=1024)
    o_r = mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o_b), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)


def test_attention_wrapper_paths_agree():
    q = randn((2, 64, 4, 32))
    k = randn((2, 64, 2, 32))
    o1 = attention(q, k, k, causal=True, use_kernel=True, interpret=True,
                   bq=32, bk=32)
    o2 = attention(q, k, k, causal=True, use_kernel=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kh,d,ps,npages,p,dtype", [
    (2, 8, 2, 32, 16, 12, 4, jnp.float32),
    (1, 4, 4, 64, 8, 20, 6, jnp.float32),
    (2, 4, 2, 16, 16, 8, 2, jnp.bfloat16),
])
def test_paged_decode_sweep(b, h, kh, d, ps, npages, p, dtype):
    q = randn((b, h, d), dtype)
    kp = randn((npages, ps, kh, d), dtype)
    vp = randn((npages, ps, kh, d), dtype)
    pt = np.full((b, p), -1, np.int32)
    pos = np.zeros((b, p), np.int32)
    lens = np.zeros((b,), np.int32)
    for bi in range(b):
        used = RNG.integers(1, p + 1)
        pages = RNG.choice(npages, used, replace=False)
        pt[bi, :used] = pages
        pos[bi, :used] = np.arange(used) * ps
        lens[bi] = (used - 1) * ps + RNG.integers(1, ps + 1)
    args = (q, kp, vp, jnp.array(pt), jnp.array(pos), jnp.array(lens))
    acc_k, m_k, l_k = paged_decode_attention(*args)
    acc_r, m_r, l_r = paged_decode_ref(*args)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               atol=tol, rtol=tol)


def test_ownership_split_merge_invariance():
    """Any partition of pages across owners merges to the same output
    -- the property that makes OP reconfiguration free."""
    b, h, kh, d, ps, npages, p = 2, 4, 2, 16, 8, 16, 6
    q = randn((b, h, d))
    kp = randn((npages, ps, kh, d))
    vp = randn((npages, ps, kh, d))
    pt = jnp.array([[0, 1, 2, 3, 4, 5], [6, 7, 8, -1, -1, -1]], jnp.int32)
    pos = jnp.array([[0, 8, 16, 24, 32, 40], [0, 8, 16, 0, 0, 0]],
                    jnp.int32)
    lens = jnp.array([44, 20], jnp.int32)
    ref = paged_decode_ref(q, kp, vp, pt, pos, lens)
    for nsplit in (2, 3):
        parts = []
        for s in range(nsplit):
            mask = (jnp.arange(p) % nsplit) == s
            pts = jnp.where(mask[None, :], pt, -1)
            parts.append(paged_decode_attention(q, kp, vp, pts, pos,
                                                lens))
        merged = merge_partials(parts)
        np.testing.assert_allclose(np.asarray(normalize(*merged)),
                                   np.asarray(normalize(*ref)),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,g,n,p,chunk,dtype", [
    (1, 64, 2, 1, 16, 8, 16, jnp.float32),
    (2, 128, 4, 2, 32, 16, 32, jnp.float32),
    (1, 64, 2, 1, 16, 8, 64, jnp.float32),     # chunk == S
    (1, 64, 2, 1, 16, 8, 16, jnp.bfloat16),
])
def test_ssd_sweep(b, s, h, g, n, p, chunk, dtype):
    x = randn((b, s, h, p), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm = randn((b, s, g, n), dtype, 0.3)
    cm = randn((b, s, g, n), dtype, 0.3)
    d = jnp.asarray(RNG.standard_normal(h) * 0.1, jnp.float32)
    y_k = ssd_scan(x, dt, a, bm, cm, d, chunk=chunk)
    y_r, _ = ssd_ref(x, dt, a, bm, cm, d)
    y_j = ssd(x, dt, a, bm, cm, d, chunk=chunk, use_kernel=False)
    tol = 4e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(y_j, np.float32),
                               np.asarray(y_r, np.float32), atol=tol,
                               rtol=tol)


def test_ssd_decode_matches_scan():
    """Token-by-token decode recurrence == full-sequence scan."""
    from repro.kernels.ssd_scan.ref import ssd_decode_step
    b, s, h, g, n, p = 1, 16, 2, 1, 8, 4
    x = randn((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm = randn((b, s, g, n), scale=0.3)
    cm = randn((b, s, g, n), scale=0.3)
    d = jnp.asarray(RNG.standard_normal(h) * 0.1, jnp.float32)
    y_full, _ = ssd_ref(x, dt, a, bm, cm, d)
    state = jnp.zeros((b, h, n, p), jnp.float32)
    for t in range(s):
        y_t, state = ssd_decode_step(state, x[:, t].astype(jnp.float32),
                                     dt[:, t], a,
                                     bm[:, t].astype(jnp.float32),
                                     cm[:, t].astype(jnp.float32), d)
        np.testing.assert_allclose(np.asarray(y_t),
                                   np.asarray(y_full[:, t]), atol=2e-4,
                                   rtol=2e-4)


# ---------------------------------------------------------------------------
# cache_transition: the planned-transition space machine on the JAX plane
# ---------------------------------------------------------------------------
from repro.kernels.cache_transition import (cache_transition,
                                            cache_transition_np,
                                            cache_transition_ref,
                                            encode_window)
from repro.kernels.interpret import resolve_interpret


@pytest.mark.parametrize("n,cap_base,seed", [
    (256, 4096, 0), (512, 8192, 1), (256, 2048, 2)])
def test_cache_transition_matches_oracles(n, cap_base, seed):
    """Pallas space machine == jnp scan oracle == plain-python
    reference: fill class decisions, Eq. 1 fast-path promotes, victim
    consumption (with the final-victim re-insert rule) and the
    occupancy trajectory."""
    rng = np.random.default_rng(seed)
    cap = cap_base + int(rng.integers(0, 2048))
    opk = rng.choice([0, 0, 0, 1, 1, 2], n).astype(np.int64)
    kd = rng.choice([0, 1, 2], n).astype(np.int64)
    pc = rng.choice([0, 0, 1, 5], n).astype(np.int64)
    plen = rng.choice([64, 128, 256], n).astype(np.int64)
    vic = rng.choice([104, 168, 296], 200).astype(np.int64)
    used0 = int(rng.integers(0, cap))
    z0 = int(rng.integers(0, 50))
    rows = encode_window(opk, kd, pc, plen, value_bytes=128)
    d1, t1, u1 = cache_transition(rows, vic, used0, z0, cap=cap)
    d2, t2, u2 = cache_transition_ref(rows, vic, used0, z0, cap=cap)
    d3, t3, u3 = cache_transition_np(np.asarray(rows), vic, used0, z0,
                                     cap=cap)
    for got, want in ((d1, d2), (t1, t2), (u1, u2)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in ((d1, d3), (t1, t3), (u1, u3)):
        np.testing.assert_array_equal(np.asarray(got), want)
    # coverage: the drive must actually consume victims
    assert int(np.asarray(t1)[-1]) >= 0


def test_cache_transition_victim_pressure():
    """A full cache under promote pressure consumes the frozen victim
    queue in order and re-inserts only final victims that fit."""
    n = 256
    opk = np.zeros(n, np.int64)          # all reads
    kd = np.ones(n, np.int64)            # all shortcut hits -> promote
    pc = np.ones(n, np.int64)
    plen = np.full(n, 1024, np.int64)
    cap = 1 << 16
    vic = np.full(300, 1064, np.int64)   # frozen LRU values
    rows = encode_window(opk, kd, pc, plen, value_bytes=1024)
    d, t, u = cache_transition(rows, vic, cap - 100, 500, cap=cap)
    d, t, u = (np.asarray(x) for x in (d, t, u))
    assert d.all()                       # zero pool huge: all promote
    assert t[-1] > 0                     # victims consumed
    assert (u <= cap).all()
    np.testing.assert_array_equal(
        (d, t, u),
        cache_transition_np(np.asarray(rows), vic, cap - 100, 500,
                            cap=cap))


@pytest.mark.parametrize("interpret,backend,want", [
    (True, "tpu", True),          # an explicit choice always wins
    (False, "cpu", False),
    (None, "cpu", True),          # observed CPU -> interpret mode
    (None, "tpu", False),         # observed TPU -> compiled (Mosaic)
])
def test_resolve_interpret_observed_backend(monkeypatch, interpret,
                                            backend, want):
    """The interpret default follows the backend the op is traced on:
    nothing but an explicit ``interpret=`` can make a TPU interpret."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(interpret) is want


def test_default_interpret_kernel_matches_oracle():
    """A kernel run with the resolved default (interpret mode on this
    CPU backend) agrees with its oracle."""
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    rng = np.random.default_rng(3)
    rows = encode_window(rng.choice([0, 1, 2], 256).astype(np.int64),
                         rng.choice([0, 1, 2], 256).astype(np.int64),
                         rng.choice([0, 2], 256).astype(np.int64),
                         np.full(256, 128, np.int64), value_bytes=128)
    vic = np.full(64, 168, np.int64)
    d1, t1, u1 = cache_transition(rows, vic, 1000, 10, cap=4096,
                                  interpret=None)
    d2, t2, u2 = cache_transition_ref(rows, vic, 1000, 10, cap=4096)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))


# ---------------------------------------------------------------- #
# batch_executor: the compiled window engine vs its numpy oracle    #
# ---------------------------------------------------------------- #

from repro.kernels import batch_executor as be  # noqa: E402
from repro.kernels.batch_executor.ops import (_BIG, _tree_build,  # noqa: E402
                                              _tree_min, _tree_set)


def _be_run_chain(seed, nslots=32, w=64, windows=3):
    """Run ``windows`` chained windows through both engines from an
    empty state, asserting bit-exact agreement (executed prefix of the
    event/out_ptr tapes, the cut reason, and all eight state arrays)."""
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(40, 2000))
    wb = int(rng.integers(8, 200))
    amr = float(rng.choice([0.5, 1.0, 3.7, 10.0, 0.125]))
    vmax = be.build_promote_table(amr)
    z = np.zeros(nslots, np.int32)
    state = be.init_state(z, z.copy(), z.copy(), z.copy(), z.copy(),
                          np.zeros(be.CNT_HIST_MAX + 1, np.int32),
                          0, 0, 0, 0, 0)
    for _ in range(windows):
        ops = rng.integers(0, 2, w).astype(np.int32)
        n = int(rng.integers(1, w + 1))
        keys = rng.integers(0, nslots, w).astype(np.int32)
        wptr = rng.integers(0, 10000, w).astype(np.int32)
        pm_ptr = rng.choice(
            np.array([be.PM_INVALID, be.PM_ABSENT, 5, 77, 1234],
                     np.int32), w,
            p=[0.08, 0.2, 0.24, 0.24, 0.24]).astype(np.int32)
        pm_len = rng.integers(1, 300, w).astype(np.int32)
        seg0 = (rng.random(w) < 0.05).astype(np.int32)
        state, _ = _be_window(state, ops, keys, wptr, pm_ptr, pm_len,
                              seg0, n, cap, wb, vmax)
    return state


def _be_window(state, *args):
    """One window through both engines from ``state``, asserting
    bit-exact agreement (the cut reason, the executed prefix of the
    event/out_ptr tapes, and every state array, registers included);
    returns the oracle's state and events of the executed prefix."""
    ne_r, st_r, ev_r, op_r, cut_r = be.fused_window_ref(state, *args)
    j = be.fused_window(tuple(np.array(a) for a in state), *args)
    ne_j, st_j = int(j[0]), j[1]
    assert (ne_r, cut_r) == (ne_j, int(j[4]))
    np.testing.assert_array_equal(ev_r[:ne_r],
                                  np.array(j[2])[:ne_r])
    np.testing.assert_array_equal(op_r[:ne_r],
                                  np.array(j[3])[:ne_r])
    for a, b in zip(st_r, st_j):
        np.testing.assert_array_equal(a, np.array(b))
    return st_r, ev_r[:ne_r]


@pytest.mark.parametrize("seed", range(8))
def test_batch_executor_matches_oracle_chain(seed):
    """Multi-window chains over a tiny slot space (heavy collisions,
    evictions, demotions, window cuts) agree bit-for-bit with the
    numpy oracle -- the fused engine's per-op contract."""
    _be_run_chain(seed)


@pytest.mark.parametrize("nslots", [2, 32, 1024])
@pytest.mark.parametrize("seed", [100, 101])
def test_batch_executor_matches_oracle_chain_slots(seed, nslots):
    """The same contract from a two-slot cache (every op collides)
    to 1024 slots, where each victim tree splits into 32 blocks."""
    _be_run_chain(seed, nslots=nslots, windows=4)


@pytest.mark.parametrize("mix", ["promote", "fill", "write"])
@pytest.mark.parametrize("seed", [200, 201])
def test_batch_executor_matches_oracle_make_space_mix(mix, seed):
    """Windows at a cache a few values large that mix ops needing no
    victim with ops whose make-space demotes or evicts -- shortcut
    promotes, miss fills or writes -- agree bit-for-bit with the numpy
    oracle, the count of make-space ops (R_MS_OPS) among the
    registers; both kinds of op ran, and the mix's own op did."""
    rng = np.random.default_rng(seed)
    nslots, w, wb = 256, 128, 120
    cap = int(rng.integers(1000, 2500))
    vmax = be.build_promote_table(0.125)    # Eq. 1 promotes readily
    z = np.zeros(nslots, np.int32)
    state = be.init_state(z, z.copy(), z.copy(), z.copy(), z.copy(),
                          np.zeros(be.CNT_HIST_MAX + 1, np.int32),
                          0, 0, 0, 0, 0)
    events, executed = [], 0
    for t in range(10):
        keys = rng.integers(0, nslots, w).astype(np.int32)
        ops = np.zeros(w, np.int32)
        if mix == "write":
            ops = (rng.random(w) < 0.7).astype(np.int32)
        elif mix == "promote" and t > 0:
            # the entries the earlier windows left, mostly shortcuts (a
            # miss fill would hold the next Eq. 1 decision to a cut)
            short = np.flatnonzero(state[0] == 1)
            vals = np.flatnonzero(state[0] == 2)
            if short.size and vals.size:
                keys = np.where(rng.random(w) < 0.8,
                                rng.choice(short, w),
                                rng.choice(vals, w)).astype(np.int32)
        wptr = rng.integers(0, 10000, w).astype(np.int32)
        pm_ptr = rng.integers(0, 10000, w).astype(np.int32)
        pm_len = rng.integers(40, 400, w).astype(np.int32)
        seg0 = np.zeros(w, np.int32)
        state, ev = _be_window(state, ops, keys, wptr, pm_ptr, pm_len,
                               seg0, w, cap, wb, vmax)
        events.append(ev)
        executed += ev.size
        # the driver drops the staleness latch once it refolds the EMA
        state[7][be.R_EMA_DIRTY] = 0
    ms_ops = int(state[7][be.R_MS_OPS])
    assert 0 < ms_ops < executed, (ms_ops, executed)
    own = {"promote": be.EV_PROMOTE, "fill": be.EV_MISS_FILL,
           "write": be.EV_WRITE}[mix]
    assert (np.concatenate(events) == own).sum() > 0


@pytest.mark.parametrize("nslots", [2, 4, 1024])
def test_victim_tree_root_is_lexicographic_argmin(nslots):
    """After every random leaf update (values from a tiny set, so
    nearly every comparison is a tie broken by key) the root is the
    numpy argmin of (value, key) with that leaf's payloads, and the
    incrementally kept tree equals one built from scratch."""
    rng = np.random.default_rng(nslots)
    pool = np.array([0, 1, 2, int(_BIG)], np.int32)
    vals = rng.choice(pool, nslots).astype(np.int32)
    pay = rng.integers(0, 1000, (2, nslots)).astype(np.int32)
    n_up = 200
    ks = rng.integers(0, nslots, n_up).astype(np.int32)
    vs = rng.choice(pool, n_up).astype(np.int32)
    ps = rng.integers(0, 1000, (n_up, 2)).astype(np.int32)

    @jax.jit
    def run(vals, pay, ks, vs, ps):
        def step(tree, x):
            tree = _tree_set(tree, x[0], x[1], (x[2], x[3]))
            return tree, jnp.stack(_tree_min(tree))
        tree = _tree_build(vals, payloads=(pay[0], pay[1]))
        return jax.lax.scan(step, tree, (ks, vs, ps[:, 0], ps[:, 1]))

    tree, roots = run(vals, pay, ks, vs, ps)
    roots = np.asarray(roots)
    for t in range(n_up):
        vals[ks[t]] = vs[t]
        pay[:, ks[t]] = ps[t]
        win = np.lexsort((np.arange(nslots), vals))[0]
        np.testing.assert_array_equal(
            roots[t], [vals[win], win, pay[0, win], pay[1, win]])
    rebuilt = _tree_build(jnp.asarray(vals), payloads=tuple(pay))
    for got, want in zip(jax.tree.leaves(tree), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batch_executor_truncation_residual():
    """A cut window reports the executed prefix length and a cut
    reason; state equals the oracle's state after exactly that prefix,
    so the host can replay the residual ops scalar-for-scalar (the
    device->host truncation contract execute_batch relies on)."""
    nslots, w = 16, 64
    vmax = be.build_promote_table(1.0)
    z = np.zeros(nslots, np.int32)
    state = be.init_state(z, z.copy(), z.copy(), z.copy(), z.copy(),
                          np.zeros(be.CNT_HIST_MAX + 1, np.int32),
                          0, 0, 0, 0, 0)
    jstate = tuple(np.array(a) for a in state)
    # all kind-0 reads; op 10 probes a segcache-backed key, which the
    # device cannot resolve -> cut there, residual [10, w) to the host
    ops = np.zeros(w, np.int32)
    keys = (np.arange(w, dtype=np.int32) % nslots)
    wptr = np.zeros(w, np.int32)
    pm_ptr = np.full(w, 500, np.int32)
    pm_len = np.full(w, 100, np.int32)
    seg0 = np.zeros(w, np.int32)
    seg0[10] = 1
    ne_r, st_r, ev_r, op_r, cut_r = be.fused_window_ref(
        state, ops, keys, wptr, pm_ptr, pm_len, seg0, w, 1 << 20, 64,
        vmax)
    j = be.fused_window(jstate, ops, keys, wptr, pm_ptr, pm_len, seg0,
                        w, 1 << 20, 64, vmax)
    assert (ne_r, cut_r) == (10, be.CUT_SEGCACHE)
    assert (int(j[0]), int(j[4])) == (ne_r, cut_r)
    np.testing.assert_array_equal(ev_r[:ne_r], np.array(j[2])[:ne_r])
    np.testing.assert_array_equal(op_r[:ne_r], np.array(j[3])[:ne_r])
    for a, b in zip(st_r, j[1]):
        np.testing.assert_array_equal(a, np.array(b))
