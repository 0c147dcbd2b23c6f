"""Data-plane micro-benchmark: batched op engine vs the scalar per-op
path, plus the fused Pallas kernels vs their jnp references.

Emits ``BENCH_dataplane.json`` next to this file so the perf trajectory
of the hot path is tracked from PR 1 onward.

Planes measured
  * simulator plane: TimedSimulation sampled-ops/s. The *scalar* side
    is the seed's per-op path -- reference caches (OrderedDict +
    lazy-heap bookkeeping, full Eq. 1 victim peek per shortcut hit)
    driven one op at a time at the seed's default sample_ops=3000. The
    *batched* side is the vectorized data plane (execute_batch: staged
    write plane + window engine, PR 2) with array-backed caches at its
    default sampling. Both produce identical statistics on the same op
    stream (property-tested in tests/test_dataplane.py +
    tests/test_writeplane.py); only the wall-clock differs. Rows cover
    read-only, read-mostly and -- since PR 2 -- the write-heavy and
    YCSB-A-like mixed (50/50 update) workloads that exercise the
    batched write plane (oplog staging, vectorized merges, bulk fills).
  * cluster plane: raw execute_batch vs per-op read()/write() on the
    same preloaded cluster, no simulation bookkeeping.
  * merge plane (PR 4): the planned merge path (MergeWindowPlan ->
    apply_merge_plan) vs the per-entry oracle on an identical staged
    log, plus per-row merge wall-time share and plan coverage inside
    the sim rows.
  * JAX plane: fused kvs_lookup (read) and log_append_merge (write)
    kernels vs their jnp references. NOTE: Pallas runs in interpret
    mode on CPU hosts, so kernel wall-clock is not meaningful there;
    the numbers are recorded for trend tracking on real accelerators.

Measurement notes: sim rows time ``repeats`` successive steady-state
windows with the collector disabled (python GC pauses otherwise add
10-20% noise to the batched side) and record both the mean and the
best window. The headline number is the best window: on this shared
host, scheduling noise between windows (+-30-50%) dwarfs the workload
variance between steady-state segments (~5%), so min-over-windows
mostly de-noises the host; the mean is recorded alongside for a
bias-free reading. Every record carries a ``host`` fingerprint and all
gates compare same-run quantities only (ratios or wall shares measured
within one invocation); historical absolutes from earlier PRs survive
as an informational ``history_untracked_hosts`` block that no gate
reads -- gating on a stale absolute measured the host, not the code.

Usage:  PYTHONPATH=src python -m benchmarks.bench_dataplane
        [--fast | --quick]   (--quick: CI smoke, a few seconds)
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import time
from collections import Counter

import numpy as np

from benchmarks.common import host_fingerprint, use_compile_cache
from repro.core import DinomoCluster, PolicyConfig, TimedSimulation, VARIANTS
from repro.data import Workload

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "BENCH_dataplane.json")

NUM_KEYS = 100_000
VALUE_BYTES = 1024
CACHE_FRAC = 0.03            # ~paper ratio: 1 GB cache vs 32 GB dataset
SEED_SAMPLE_OPS = 3000       # the seed's TimedSimulation default

# Historical recordings (sampled-ops/s) from earlier PRs' runs.  These
# came from a drifting shared 2-vCPU host with no provenance, so they
# are kept ONLY as informational trajectory markers: no gate compares
# against them (a gate on a stale absolute measured the host, not the
# code -- `meets_write_target` did exactly that until ISSUE 9).
PR1_BATCHED_WRITE_HEAVY = 31_299.0
PR2_BATCHED_WRITE_HEAVY = 83_000.0
PR3_BATCHED_WRITE_HEAVY = 66_000.0
PR3_WRITE_HEAVY_SPEEDUP = 3.4    # same-run ratio: host-portable


def _cluster(reference: bool, num_kns: int = 4,
             num_keys: int = NUM_KEYS) -> DinomoCluster:
    c = DinomoCluster(VARIANTS["dinomo"], num_kns=num_kns,
                      cache_bytes=int(num_keys * VALUE_BYTES * CACHE_FRAC),
                      value_bytes=VALUE_BYTES, num_buckets=1 << 17,
                      segment_capacity=512,
                      policy=PolicyConfig(grace_period_s=1e9, epoch_s=1e9),
                      reference_cache=reference)
    c.load(((k, f"v{k}") for k in range(num_keys)), warm=True)
    return c


def bench_sim(mix: str, zipf: float, steps: int, num_keys: int,
              repeats: int = 2, distribution: str = "zipfian",
              jit: bool = False) -> dict:
    """Sampled-ops/s through TimedSimulation, scalar vs batched (and,
    with ``jit=True``, the compiled batch executor as a third leg with
    its engine-span breakdown -- the same-run basis for the write-plane
    gate)."""
    from repro.core.telemetry import ENGINE_SPANS
    out = {}
    stats = {}
    plan_counts = Counter()        # every leg's cluster, warm-up included
    gc_was_enabled = gc.isenabled()
    gc.disable()
    legs = [("scalar", True, False, SEED_SAMPLE_OPS, None),
            ("batched", False, True, None, None)]
    if jit:
        legs.append(("jit", False, True, None, "jit"))
    try:
        for label, reference, batched, sample_ops, engine in legs:
            c = _cluster(reference, num_keys=num_keys)
            w = Workload(num_keys=num_keys, zipf=zipf, mix=mix, seed=0,
                         distribution=distribution)
            kw = {} if sample_ops is None else {"sample_ops": sample_ops}
            sim = TimedSimulation(c, w.timed_batched if batched else w.timed,
                                  dt=1.0, batched=batched, engine=engine,
                                  **kw)
            sim.run(2.0, lambda t: 1e8)                 # warm-up
            before = c.telemetry.snapshot()
            walls = []
            for _ in range(repeats):
                gc.collect()
                t0 = time.perf_counter()
                sim.run(sim.now + steps, lambda t: 1e8)
                walls.append(time.perf_counter() - t0)
            spans, counts = _delta(before, c.telemetry.snapshot())
            plan_counts.update(c.telemetry.counts)
            best = min(walls)
            out[label] = {
                "sampled_ops_per_s": steps * sim.sample_ops / best,
                "sampled_ops_per_s_mean":
                    steps * sim.sample_ops * len(walls) / sum(walls),
                "sample_ops": sim.sample_ops,
                "wall_s": best,
                # PR 4 tracking: share of the measured wall spent in the
                # staged merge plane (merge_budget + merge_all), and the
                # fraction of merged entries the MergeWindowPlan covered
                "merge_wall_share": spans.get("pool.merge", 0.0)
                / sum(walls),
                "merge_plan_coverage": _coverage(counts, "merge_plan",
                                                 "entries"),
            }
            if batched:
                # window-engine wall breakdown over the measured
                # repeats: "bookkeeping" is everything the host does
                # around the window decisions (planning, folding,
                # residency sync) -- the compiled executor's dispatch
                # itself is excluded
                wall_total = sum(walls)
                wall = {k: spans.get(v, 0.0) for k, v in ENGINE_SPANS.items()}
                book = sum(v for k, v in wall.items() if k != "jit_dispatch")
                out[label]["engine_wall"] = wall
                out[label]["bookkeeping_share"] = book / wall_total
                stats[label] = c.aggregate_stats()
    finally:
        if gc_was_enabled:
            gc.enable()
    if jit:
        # decision-for-decision equivalence of the compiled leg, same
        # run, same op stream (the property-tested contract, asserted
        # here so a bench record can never come from diverged engines)
        assert stats["jit"] == stats["batched"], \
            f"engine divergence: {stats['jit']} vs {stats['batched']}"
        out["jit_speedup_over_scalar"] = (
            out["jit"]["sampled_ops_per_s"]
            / out["scalar"]["sampled_ops_per_s"])
    out["speedup"] = (out["batched"]["sampled_ops_per_s"]
                      / out["scalar"]["sampled_ops_per_s"])
    out["plan_coverage"] = _coverage(plan_counts, "plan", "ops")
    return out


def _delta(before: dict, after: dict):
    """Span totals and counts between two ``Telemetry.snapshot()``s."""
    spans = {n: r["total_s"] - before["spans"].get(n, {}).get("total_s", 0.0)
             for n, r in after["spans"].items()}
    counts = Counter(after["counts"])
    counts.subtract(before["counts"])
    return spans, counts


def _coverage(counts, plane: str, unit: str) -> float:
    """Fraction of window ops (``plan``, PR 3) or merged entries
    (``merge_plan``, PR 4) that the planned path covered, vs scalar
    replay."""
    planned = counts[f"{plane}.planned_{unit}"]
    total = planned + counts[f"{plane}.replayed_{unit}"]
    return planned / total if total else 0.0


def bench_merge_plane(n_entries: int = 40_000, reps: int = 3) -> dict:
    """Merge-plane micro-bench: the planned path (MergeWindowPlan ->
    apply_merge_plan) vs the per-entry oracle (vectorized=False), same
    entries, same pre-state -- the same-run scalar baseline for the
    staged merge plane itself. Times merge_all over a fully staged
    write-heavy log (zipf-duplicated keys: in-place updates, fresh
    claims and within-window supersession)."""
    from repro.core.dpm_pool import DPMPool
    out = {}
    for label, vec in (("scalar_per_entry", False), ("planned", True)):
        walls = []
        cov = 0.0
        for _ in range(reps):
            rng = np.random.default_rng(1)
            pool = DPMPool(num_buckets=1 << 17, segment_capacity=512,
                           vectorized=vec)
            pool.register_kn("kn1")
            keys = (rng.zipf(1.5, n_entries) % 100_000).tolist()
            pool.log_write_batch("kn1", keys,
                                 [f"v{i}" for i in range(n_entries)],
                                 [64] * n_entries)
            before = pool.telemetry.snapshot()
            t0 = time.perf_counter()
            pool.merge_all()
            walls.append(time.perf_counter() - t0)
            cov = _coverage(_delta(before, pool.telemetry.snapshot())[1],
                            "merge_plan", "entries")
        out[label] = {"entries_per_s": n_entries / min(walls),
                      "wall_s": min(walls),
                      "plan_coverage": cov}
    out["speedup"] = (out["planned"]["entries_per_s"]
                      / out["scalar_per_entry"]["entries_per_s"])
    out["n_entries"] = n_entries
    return out


def bench_cluster(mix: str, zipf: float, n_ops: int,
                  num_keys: int) -> dict:
    """Raw data-plane ops/s: execute_batch vs per-op read()/write()."""
    w1 = Workload(num_keys=num_keys, zipf=zipf, mix=mix, seed=0)
    w2 = Workload(num_keys=num_keys, zipf=zipf, mix=mix, seed=0)
    a, b = _cluster(True), _cluster(False)
    vals = [f"w{i}" for i in range(n_ops)]
    # warm both with the identical stream
    for i, (kind, key) in enumerate(w1.ops(n_ops)):
        if kind == "read":
            a.read(key)
        else:
            a.write(key, vals[i])
    kinds, keys = w2.ops_arrays(n_ops)
    for s in range(0, n_ops, SEED_SAMPLE_OPS):
        b.execute_batch(kinds[s:s + SEED_SAMPLE_OPS],
                        keys[s:s + SEED_SAMPLE_OPS],
                        values=vals[s:s + SEED_SAMPLE_OPS])
    # measured pass
    ops2 = w1.ops(n_ops)
    kinds2, keys2 = w2.ops_arrays(n_ops)
    t0 = time.perf_counter()
    for i, (kind, key) in enumerate(ops2):
        if kind == "read":
            a.read(key)
        else:
            a.write(key, vals[i])
    dt_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for s in range(0, n_ops, SEED_SAMPLE_OPS):
        b.execute_batch(kinds2[s:s + SEED_SAMPLE_OPS],
                        keys2[s:s + SEED_SAMPLE_OPS],
                        values=vals[s:s + SEED_SAMPLE_OPS])
    dt_b = time.perf_counter() - t0
    sa, sb = a.aggregate_stats(), b.aggregate_stats()
    assert sa == sb, f"stat divergence: {sa} vs {sb}"
    return {
        "scalar_ops_per_s": n_ops / dt_s,
        "batched_ops_per_s": n_ops / dt_b,
        "speedup": dt_s / dt_b,
        "rts_per_op": sa["rts_per_op"],
        "hit_ratio": sa["hit_ratio"],
    }


def bench_kernel(nb: int = 1 << 12, nkeys: int = 4096, width: int = 8,
                 batch: int = 2048, reps: int = 5) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core.clht import clht_init, clht_insert
    from repro.core.log import heap_append, heap_init, segment_init
    from repro.kernels.clht_probe import kvs_lookup, kvs_lookup_ref
    from repro.kernels.log_merge import (log_append_merge,
                                         log_append_merge_ref)

    rng = np.random.default_rng(0)
    keys = rng.choice(10 * nkeys, nkeys, replace=False).astype(np.int32)
    t = clht_init(nb)
    heap = heap_init(2 * nkeys + 8, width)
    heap, ptrs = heap_append(
        heap, jnp.arange(nkeys * width, dtype=jnp.int32)
        .reshape(nkeys, width))
    t, *_ = clht_insert(t, jnp.array(keys), ptrs)
    probe = jnp.array(rng.choice(keys, batch).astype(np.int32))

    def timed(fn, *args):
        r = fn(*args)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / reps / batch * 1e6

    # write-path: append+merge a batch into a fresh segment
    wseg = segment_init(max(batch + 8, 16))
    wkeys = jnp.array(rng.choice(keys, batch).astype(np.int32))
    wvals = jnp.zeros((batch, width), jnp.int32)
    return {
        "fused_lookup_us_per_key": timed(kvs_lookup, t, heap, probe),
        "jnp_lookup_ref_us_per_key": timed(kvs_lookup_ref, t, heap, probe),
        "fused_append_merge_us_per_key": timed(
            log_append_merge, t, wseg, heap, wkeys, wvals),
        "jnp_append_merge_ref_us_per_key": timed(
            log_append_merge_ref, t, wseg, heap, wkeys, wvals),
        "batch": batch,
        "interpret_mode": True,
        "note": ("Pallas interpret mode on CPU: kernel timing tracks "
                 "trend only; the jnp references are the CPU-meaningful "
                 "numbers"),
    }


SIM_ROWS = (
    ("read_only", 0.99, "zipfian"),
    ("read_mostly_update", 0.99, "zipfian"),
    ("read_only", 2.0, "zipfian"),
    # write plane (PR 2): the write-heavy row is the PR-1 regression
    # anchor; z0.99 is the YCSB-A-like 50/50 mixed workload
    ("write_heavy_update", 0.5, "zipfian"),
    ("write_heavy_update", 0.99, "zipfian"),
    # YCSB-D-like: read-mostly inserts with the latest distribution
    # (reads chase the insert frontier; PR 3 satellite)
    ("read_mostly_insert", 0.99, "latest"),
)


def main(fast: bool = False, quick: bool = False) -> dict:
    if quick:
        steps, n_ops, repeats = 2, 9000, 1
    elif fast:
        steps, n_ops, repeats = 4, 20_000, 1
    else:
        steps, n_ops, repeats = 8, 60_000, 2
    num_keys = NUM_KEYS
    sims = {}
    for mix, zipf, dist in SIM_ROWS:
        name = f"{mix}_z{zipf}" if dist == "zipfian" \
            else f"{mix}_z{zipf}_{dist}"
        # the gated write-plane row also runs the compiled executor leg
        jit = (mix, zipf) == ("write_heavy_update", 0.5)
        print(f"# sim plane: {name}", flush=True)
        sims[name] = bench_sim(mix, zipf, steps, num_keys,
                               repeats=repeats, distribution=dist,
                               jit=jit)
        msg = (f"  scalar {sims[name]['scalar']['sampled_ops_per_s']:.0f} "
               f"ops/s  batched "
               f"{sims[name]['batched']['sampled_ops_per_s']:.0f} ops/s  "
               f"{sims[name]['speedup']:.1f}x")
        if jit:
            msg += (f"  jit {sims[name]['jit']['sampled_ops_per_s']:.0f} "
                    f"ops/s (bookkeeping share "
                    f"{sims[name]['jit']['bookkeeping_share']:.2f})")
        print(msg, flush=True)
    print("# cluster plane", flush=True)
    clu = bench_cluster("read_only", 0.99, n_ops, num_keys)
    print(f"  scalar {clu['scalar_ops_per_s']:.0f}  batched "
          f"{clu['batched_ops_per_s']:.0f}  {clu['speedup']:.1f}x",
          flush=True)
    print("# merge plane (planned vs per-entry oracle)", flush=True)
    mp = bench_merge_plane(n_entries=4000 if quick
                           else (10_000 if fast else 40_000),
                           reps=1 if quick else (2 if fast else 3))
    print(f"  scalar {mp['scalar_per_entry']['entries_per_s']:.0f} "
          f"entries/s  planned {mp['planned']['entries_per_s']:.0f} "
          f"entries/s  {mp['speedup']:.1f}x  coverage "
          f"{mp['planned']['plan_coverage']:.2f}", flush=True)
    print("# JAX plane (interpret mode)", flush=True)
    kern = bench_kernel(batch=256 if quick else (512 if fast else 2048),
                        reps=1 if quick else (2 if fast else 5))
    best = max(s["speedup"] for s in sims.values())
    wh_row = sims["write_heavy_update_z0.5"]
    wh = wh_row["batched"]["sampled_ops_per_s"]
    jit_speedup = wh_row["jit_speedup_over_scalar"]
    jit_book = wh_row["jit"]["bookkeeping_share"]
    host_book = wh_row["batched"]["bookkeeping_share"]
    record = {
        "config": {"num_keys": num_keys, "value_bytes": VALUE_BYTES,
                   "cache_frac": CACHE_FRAC, "num_kns": 4,
                   "scalar_sample_ops": SEED_SAMPLE_OPS,
                   "steps": steps, "repeats": repeats},
        "host": host_fingerprint(),
        "simulator_plane": sims,
        "cluster_plane": clu,
        "jax_plane": kern,
        "best_sim_speedup": best,
        "target_speedup": 10.0,
        "meets_target": best >= 10.0,
        "write_plane": {
            "row": "write_heavy_update_z0.5",
            "batched_ops_per_s": wh,
            # informational trajectory only -- absolutes from earlier
            # PRs' unfingerprinted hosts; no gate reads these
            "history_untracked_hosts": {
                "pr1_batched_ops_per_s": PR1_BATCHED_WRITE_HEAVY,
                "pr2_batched_ops_per_s": PR2_BATCHED_WRITE_HEAVY,
                "pr3_batched_ops_per_s": PR3_BATCHED_WRITE_HEAVY,
                "improvement_over_pr1_batched":
                    wh / PR1_BATCHED_WRITE_HEAVY,
            },
            "speedup_over_scalar_same_run": wh_row["speedup"],
            # ISSUE 4 tracking: the same-run (host-portable) ratio the
            # PR 3 run recorded
            "pr3_speedup_over_scalar_same_run": PR3_WRITE_HEAVY_SPEEDUP,
            "speedup_improves_on_pr3":
                wh_row["speedup"] > PR3_WRITE_HEAVY_SPEEDUP,
            # ISSUE 9 gate, same-run quantities only: the compiled
            # executor either reaches the 5x write-plane target over
            # the scalar path outright, or (interpret-mode allowance:
            # XLA CPU runs the window sequentially, so absolute wall
            # cannot beat the host's numpy loop) it must collapse the
            # host-bookkeeping wall share from ~90% to <= 40% -- the
            # floor ISSUE 9 set out to remove; see ROADMAP "Compiled
            # batch executor"
            "jit_speedup_over_scalar_same_run": jit_speedup,
            "target_jit_speedup": 5.0,
            "host_engine_bookkeeping_share": host_book,
            "jit_engine_bookkeeping_share": jit_book,
            "target_jit_bookkeeping_share": 0.40,
            "jit_engine_wall": wh_row["jit"]["engine_wall"],
            "meets_write_target":
                jit_speedup >= 5.0 or jit_book <= 0.40,
            "plan_coverage": wh_row["plan_coverage"],
            "ycsb_a_like_ops_per_s":
                sims["write_heavy_update_z0.99"]["batched"]
                    ["sampled_ops_per_s"],
            "ycsb_d_like_latest_ops_per_s":
                sims["read_mostly_insert_z0.99_latest"]["batched"]
                    ["sampled_ops_per_s"],
        },
        "merge_plane": {
            "micro": mp,
            "write_heavy_merge_wall_share": {
                "scalar": wh_row["scalar"]["merge_wall_share"],
                "batched": wh_row["batched"]["merge_wall_share"],
            },
            "write_heavy_merge_plan_coverage":
                wh_row["batched"]["merge_plan_coverage"],
            "target_plan_coverage": 0.95,
            "meets_plan_coverage":
                wh_row["batched"]["merge_plan_coverage"] >= 0.95,
        },
    }
    # quick/fast smoke runs must not clobber the tracked full-run record
    out = OUT if not (fast or quick) else \
        OUT.replace(".json", ".smoke.json")
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    wp = record["write_plane"]
    print(f"\nwrote {out}; best sim-plane speedup {best:.1f}x; "
          f"write-heavy jit speedup over scalar "
          f"{wp['jit_speedup_over_scalar_same_run']:.1f}x, bookkeeping "
          f"share {host_book:.2f} (host) -> {jit_book:.2f} (jit); "
          f"meets_write_target={wp['meets_write_target']}")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: a couple of steps per row")
    args = ap.parse_args()
    use_compile_cache()
    main(args.fast, args.quick)
