#!/usr/bin/env python3
"""Chip smoke: drive the store's compiled data plane once on a TPU.

Builds a DINOMO cluster at the paper's shapes (Sec. 5: 4 KNs, 1 KB
values, zipf 0.99, KN cache = dataset / 32) and a host-engine twin,
runs YCSB C, B and A batches through ``execute_batch(engine="jit")``
on the first and ``engine="host"`` on the twin, and requires the two to
agree exactly: statistics, every read value, cache state and pool
integrity.  It fails unless the device executed ops.

This is a bring-up check, not a benchmark: it prints set-up times,
where ops ran and device memory, never a throughput.  It refuses to
run without a TPU.

    python chip_smoke.py [--keys N]

On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PAPER_KEYS = 1 << 25             # 32 GB of 1 KB values (paper Sec. 5)
DEFAULT_KEYS = 1 << 22           # the cut that one v5e chip holds here
NUM_KNS = 4
ZIPF = 0.99
# YCSB C, B, A (data/ycsb.py MIXES)
SMOKE_MIXES = ("read_only", "read_mostly_update", "write_heavy_update")
MERGE_PER_OP = 4                 # benchmarks.common.execute_ops_batched
# ops per execute_batch call: windows then exceed MIN_SPAN and reach
# W_MAX (core/jit_engine.py)
BATCH = 8192
BATCHES_PER_MIX = 3
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_or_exit():
    """The device line, then a hard stop unless JAX found a TPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d.platform!r});"
                 f" this smoke never runs on another backend")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _import_repo():
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def _pow2(n: int) -> str:
    return f"2^{n.bit_length() - 1}" if n & (n - 1) == 0 else str(n)


def _compile_timer():
    """Sum XLA backend compile seconds, as JAX reports them."""
    import jax
    tot = {"s": 0.0, "n": 0}

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tot["s"] += secs
            tot["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return tot


def _same_caches(a, b) -> list[str]:
    import numpy as np
    bad = []
    for name in a.kns:
        ca, cb = a.kns[name].cache, b.kns[name].cache
        for f in ("kind", "ptr", "length", "count", "stamp"):
            if not np.array_equal(getattr(ca, f), getattr(cb, f)):
                bad.append(f"{name}.{f}")
        for f in ("used", "_clock", "_nvals", "_nshort",
                  "_zero_shortcuts", "_cnt_hist", "avg_miss_rts"):
            if getattr(ca, f) != getattr(cb, f):
                bad.append(f"{name}.{f}")
    return bad


def donation_honored(cluster) -> bool:
    """One empty dispatch at the served slot geometry (no new compile):
    True iff the executor consumed the state buffers it was given."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.jit_engine import W_MAX
    from repro.kernels import batch_executor as be
    kn = next(iter(cluster.kns.values()))
    pad = 1 << (kn.cache.kind.shape[0] - 1).bit_length()
    z = np.zeros(pad, np.int32)
    state = tuple(jnp.asarray(a) for a in be.init_state(
        z, z, z, z, z, np.zeros(be.CNT_HIST_MAX + 1, np.int32),
        0, 1, 0, 0, 0))
    w = np.zeros(W_MAX, np.int32)
    out = be.fused_window(state, w, w, w, w, w, w, 0, 1 << 20,
                          cluster.value_bytes,
                          jnp.asarray(be.build_promote_table(2.0)))
    out[0].block_until_ready()
    return all(a.is_deleted() for a in state)


def run(keys: int, batches: int = BATCHES_PER_MIX, seed: int = SEED):
    """The mixes on the chip cluster and its host twin; raises on any
    disagreement or when no op ran on the device.  Returns the chip
    cluster."""
    _import_repo()
    from benchmarks.common import VALUE_BYTES, build_cluster
    from repro.data import Workload
    # the multi-pod dry run forces 512 host devices when imported
    assert "repro.launch.dryrun" not in sys.modules
    compiles = _compile_timer()
    cache_bytes = keys * VALUE_BYTES // 32
    log(f"config: variant=dinomo kns={NUM_KNS} keys={keys} "
        f"value_bytes={VALUE_BYTES} kn_cache_bytes={cache_bytes} "
        f"zipf={ZIPF} batch={BATCH} batches_per_mix={batches}")
    log(f"reduced: keys {_pow2(PAPER_KEYS)} -> {_pow2(keys)}")
    clusters = {}
    for eng in ("jit", "host"):
        t0 = time.perf_counter()
        clusters[eng] = build_cluster("dinomo", NUM_KNS,
                                      cache_bytes=cache_bytes,
                                      num_keys=keys, seed=seed)
        log(f"setup: load+warm ({eng} cluster) "
            f"{time.perf_counter() - t0:.1f} s (host wall)")
    chip, twin = clusters["jit"], clusters["host"]

    first = True
    for m, mix in enumerate(SMOKE_MIXES):
        t0 = time.perf_counter()
        w = Workload(num_keys=keys, zipf=ZIPF, mix=mix, seed=seed + m)
        kinds, kk = w.ops_arrays(BATCH * batches)
        log(f"setup: workload {mix} {time.perf_counter() - t0:.1f} s")
        for b in range(batches):
            lo, hi = b * BATCH, (b + 1) * BATCH
            vals = (lambda j, base=m * (1 << 30) + lo: f"w{base + j}")
            t0 = time.perf_counter()
            rj = chip.execute_batch(kinds[lo:hi], kk[lo:hi], values=vals,
                                    collect_values=True, engine="jit")
            if first:
                log(f"setup: first jit batch {time.perf_counter() - t0:.1f}"
                    f" s (host wall, includes upload and compile)")
                first = False
            rh = twin.execute_batch(kinds[lo:hi], kk[lo:hi], values=vals,
                                    collect_values=True, engine="host")
            if (rj.executed, rj.writes) != (rh.executed, rh.writes) \
                    or rj.values != rh.values:
                raise AssertionError(f"{mix} batch {b}: executed ops or "
                                     f"read values differ")
            for c in (chip, twin):
                c.advance_merge(MERGE_PER_OP * BATCH)
        sj, sh = chip.aggregate_stats(), twin.aggregate_stats()
        if sj != sh:
            raise AssertionError(f"{mix}: stats differ\n{sj}\n{sh}")
        log(f"agree: {mix} stats={json.dumps(sj, sort_keys=True)}")
    for c in (chip, twin):
        c.advance_merge(1 << 30)
    bad = _same_caches(chip, twin)
    if bad:
        raise AssertionError(f"cache state differs: {bad[:8]}")
    ij, ih = chip.pool.verify_integrity(), twin.pool.verify_integrity()
    if ij or ih or ij != ih:
        raise AssertionError(f"pool integrity: jit={ij[:4]} host={ih[:4]}")
    log("agree: cache state, pool integrity (verify_integrity() == [])")

    eng = chip._jit
    if eng is None:
        raise AssertionError("no window reached the jit engine")
    tel = chip.telemetry
    cnt = tel.counts
    host_ops = {k.split(".", 1)[1]: v for k, v in sorted(cnt.items())
                if k.startswith("host_ops.")}
    log(f"device: dispatches={cnt['dispatches']} "
        f"device_ops={cnt['device_ops']}")
    log(f"host: replayed ops by reason {json.dumps(host_ops)}")
    if cnt["device_ops"] <= 0:
        raise AssertionError("no op ran on the device")
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory: resident_state_bytes_peak={eng.resident_bytes_peak} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    log(f"setup: upload {tel.total('jit.upload'):.1f} s (host wall), "
        f"{cnt['jit.upload_bytes']} B; backend compile "
        f"{compiles['s']:.1f} s over {compiles['n']} compiles")
    return chip


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys", type=int, default=DEFAULT_KEYS)
    args = ap.parse_args()
    device = tpu_or_exit()
    _import_repo()
    from benchmarks.common import use_compile_cache
    log(f"setup: compile cache {use_compile_cache()}")
    chip = run(args.keys)
    donated = donation_honored(chip)
    log(f"device: executor state buffers donated={donated}")
    if not donated:
        raise AssertionError("the executor did not consume its donated "
                             "state")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
