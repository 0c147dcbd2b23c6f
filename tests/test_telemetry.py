"""Per-cluster spans and counters (``core/telemetry.py``): self time,
isolation between clusters, reset, and the profiler annotations that
put every span on the device trace's clock."""

import gc
import glob
import time

import pytest

from repro.core import DINOMO, DinomoCluster, Telemetry
from repro.core.telemetry import ENGINE_SPANS, ENGINE_WALL, PREFIX
from repro.data import Workload


def _cluster():
    c = DinomoCluster(DINOMO, num_kns=2, cache_bytes=1 << 16,
                      value_bytes=64, num_buckets=1 << 10,
                      segment_capacity=64)
    c.load(((k, f"v{k}") for k in range(800)), warm=True)
    return c


def _batch(seed, n=600):
    w = Workload(num_keys=800, zipf=0.99, mix="write_heavy_update",
                 seed=seed)
    return w.ops_arrays(n)


def test_nested_spans_total_is_self_plus_children():
    tel = Telemetry()
    with tel.span("outer"):
        time.sleep(0.002)
        with tel.span("inner", kn="kn1"):
            time.sleep(0.003)
        with tel.span("inner", kn="kn2"):
            with tel.span("leaf"):
                time.sleep(0.001)
    snap = tel.snapshot()["spans"]
    outer, inner, leaf = snap["outer"], snap["inner"], snap["leaf"]
    assert (outer["calls"], inner["calls"], leaf["calls"]) == (1, 2, 1)
    assert outer["total_s"] == pytest.approx(
        outer["self_s"] + inner["total_s"], abs=1e-12)
    assert inner["total_s"] == pytest.approx(
        inner["self_s"] + leaf["total_s"], abs=1e-12)
    assert leaf["self_s"] == leaf["total_s"] >= 0.001
    assert outer["self_s"] >= 0.002


def test_a_span_closes_on_an_exception():
    tel = Telemetry()
    with pytest.raises(KeyError):
        with tel.span("outer"):
            with tel.span("inner"):
                raise KeyError("x")
    with tel.span("after"):
        pass
    snap = tel.snapshot()["spans"]
    assert {n: r["calls"] for n, r in snap.items()} == \
        {"outer": 1, "inner": 1, "after": 1}
    # the raise popped both: "after" is nobody's child
    assert snap["outer"]["self_s"] == pytest.approx(
        snap["outer"]["total_s"] - snap["inner"]["total_s"], abs=1e-12)


def test_two_clusters_keep_their_own_spans_and_counts():
    a, b = _cluster(), _cluster()
    a.telemetry.reset()
    b.telemetry.reset()
    kinds, keys = _batch(1)
    a.execute_batch(kinds, keys, values=lambda i: f"w{i}", engine="jit")
    sa, sb = a.telemetry.snapshot(), b.telemetry.snapshot()
    assert sa["spans"]["cluster.execute_batch"]["calls"] == 1
    assert sa["counts"]["dispatches"] > 0
    assert sb == {"spans": {}, "counts": {}}
    b.execute_batch(kinds, keys, values=lambda i: f"w{i}")
    sb = b.telemetry.snapshot()
    assert "jit.dispatch" not in sb["spans"]
    assert sb["spans"]["host.plan"]["calls"] > 0
    assert a.telemetry.snapshot() == sa
    # the pool, its index and the jit driver share the cluster's
    assert a.pool.telemetry is a.telemetry
    assert a.pool.index.telemetry is a.telemetry
    assert a._jit.telemetry is a.telemetry
    assert a._jit.counts is a.telemetry.counts


def test_reset_clears_spans_and_counts_and_keeps_the_batch_seq():
    c = _cluster()
    kinds, keys = _batch(2)
    c.execute_batch(kinds, keys, values=lambda i: f"w{i}", engine="jit")
    tel = c.telemetry
    assert tel.spans and tel.counts and tel.batch == 1
    counts = tel.counts
    tel.reset()
    assert tel.snapshot() == {"spans": {}, "counts": {}}
    assert tel.counts is counts and tel.batch == 1
    c.execute_batch(kinds, keys, values=lambda i: f"w{i}", engine="jit")
    assert tel.batch == 2
    assert tel.spans["cluster.execute_batch"][2] == 1


def test_batch_spans_nest_under_execute_batch():
    c = _cluster()
    c.telemetry.reset()
    kinds, keys = _batch(3)
    c.execute_batch(kinds, keys, values=lambda i: f"w{i}", engine="jit")
    snap = c.telemetry.snapshot()["spans"]
    for name in ("cluster.route", "cluster.write_plan",
                 "cluster.probe_prefetch", "cluster.finalize",
                 "jit.probe_map", "jit.upload", "jit.prep", "jit.dispatch",
                 "jit.fold", "jit.sync"):
        assert snap[name]["calls"] > 0, name
    top = snap["cluster.execute_batch"]
    inner = sum(r["total_s"] for n, r in snap.items()
                if n != "cluster.execute_batch")
    assert top["self_s"] == pytest.approx(top["total_s"] - inner, abs=1e-9)
    counts = c.telemetry.counts
    assert counts["jit.upload_bytes"] > 0
    assert counts["jit.sync_bytes"] == counts["jit.upload_bytes"]


def test_make_space_ops_count_device_ops_that_freed_room():
    """``jit.make_space_ops`` counts the device ops whose make-space
    demoted or evicted: some of a write-heavy batch at a full cache,
    never more than the device ran, nor more than the demotions and
    evictions."""
    c = DinomoCluster(DINOMO, num_kns=2, cache_bytes=1 << 13,
                      value_bytes=64, num_buckets=1 << 10,
                      segment_capacity=64)
    c.load(((k, f"v{k}") for k in range(800)), warm=True)
    c.telemetry.reset()
    c.reset_stats()
    kinds, keys = _batch(5)
    c.execute_batch(kinds, keys, values=lambda i: f"w{i}", engine="jit")
    counts = c.telemetry.counts
    moved = sum(kn.cache.stats.demotions + kn.cache.stats.evictions
                for kn in c.kns.values())
    assert 0 < counts["jit.make_space_ops"] <= counts["device_ops"]
    assert counts["jit.make_space_ops"] <= moved


def test_stall_merges_inside_a_batch_are_pool_merge_spans():
    c = DinomoCluster(DINOMO, num_kns=2, cache_bytes=1 << 16,
                      value_bytes=64, num_buckets=1 << 10,
                      segment_capacity=16)
    c.load(((k, f"v{k}") for k in range(800)), warm=True)
    c.telemetry.reset()
    kinds, keys = _batch(4, 2000)
    c.execute_batch(kinds, keys, values=lambda i: f"w{i}")
    assert c.aggregate_stats()["write_stalls"] > 0
    merge = c.telemetry.snapshot()["spans"]["pool.merge"]
    assert merge["calls"] > 0 and merge["total_s"] > 0
    assert c.telemetry.counts["merge_plan.planned_entries"] \
        + c.telemetry.counts["merge_plan.replayed_entries"] > 0


def test_spans_accumulate_with_no_profiler_active():
    tel = Telemetry()
    for _ in range(3):
        with tel.span("jit.sync", kn="kn1"):
            pass
    assert tel.spans["jit.sync"][2] == 3
    assert tel.total("jit.sync") >= 0.0
    assert tel.total("never.opened") == 0.0


def test_engine_wall_sums_the_engine_spans_of_live_clusters():
    gc.collect()     # no other cluster's spans may leave the sum midway
    tel = Telemetry()
    before = dict(ENGINE_WALL)
    assert list(before) == list(ENGINE_SPANS)
    with tel.span("jit.dispatch"):
        time.sleep(0.001)
    with tel.span("cluster.route"):
        pass
    after = dict(ENGINE_WALL)
    assert after["jit_dispatch"] - before["jit_dispatch"] == \
        pytest.approx(tel.total("jit.dispatch"))
    assert {k for k in after if after[k] != before[k]} == {"jit_dispatch"}
    with pytest.raises(TypeError):
        ENGINE_WALL["jit_dispatch"] = 0.0


def test_spans_land_in_the_profiler_trace_with_their_ids(tmp_path):
    import jax
    from jax.profiler import ProfileData
    c = _cluster()
    kinds, keys = _batch(5)
    c.execute_batch(kinds, keys, values=lambda i: f"w{i}", engine="jit")
    jax.profiler.start_trace(str(tmp_path))
    try:
        c.execute_batch(kinds, keys, values=lambda i: f"w{i}",
                        engine="jit")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(PREFIX)]
    names = {n for n, _ in events}
    assert {PREFIX + "cluster.execute_batch", PREFIX + "jit.dispatch",
            PREFIX + "jit.upload", PREFIX + "jit.sync"} <= names
    assert all(ids["batch"] == 2 for _, ids in events)
    kns = {ids["kn"] for n, ids in events if n == PREFIX + "jit.dispatch"}
    assert kns and kns <= set(c.kns)
    assert all("kn" not in ids for n, ids in events
               if n == PREFIX + "cluster.execute_batch")
