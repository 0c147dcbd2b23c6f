#!/usr/bin/env python3
"""The store's chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses any backend but a TPU (exit non-zero, no result).  Set-up builds
the cell's cluster with the store's own ``DinomoCluster(...)`` and
``load(warm=True)``, the traffic from ``--seed``, and runs the traffic's
warm-up steps, so that the executor is compiled (or loaded from the
compile cache) before the window.  ``--trace 0`` then measures the
closed loop for ``--seconds`` and reports the cell's end-to-end
metrics; ``--trace 1`` profiles ``TRACE_STEPS`` steps and reports the
per-layer metrics.  Then every read answered, every touched
key read back through the index after all merges, and the pool's
integrity are compared with a plain reference (``bench/lib/reference``).

The last line of standard output is the result, one JSON object; all
else goes to standard error, ending with each number compared beside
its limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.lib import device as dev  # noqa: E402
from bench.lib import harness, reference, trace  # noqa: E402
from bench.lib.harness import log  # noqa: E402

# One step of fused_window records ~4.7M device operation events (a set
# per loop iteration), just under what the trace buffers hold: a second
# step overflows them and whole program executions vanish from the
# trace.  The record leaves the device metrics out where that happens.
TRACE_STEPS = 1


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def traced_window(run, steps: int, seconds: float):
    """Profile ``steps`` steps (``seconds`` at most); returns the
    window and the trace's summary."""
    import jax
    from jax.profiler import ProfileData
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # benchmark spans, not every call
    # programs and their operations only (TRACE_STEPS)
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            win = run.window(seconds, max_steps=steps)
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: stop_trace {time.perf_counter() - t0:.1f} s")
        t0 = time.perf_counter()
        path = trace.newest_xspace(log_dir)
        size = os.path.getsize(path)
        summary = trace.reduce_profile(ProfileData.from_file(path))
        log(f"trace: {size} B xplane, reduced in "
            f"{time.perf_counter() - t0:.1f} s; programs "
            f"{json.dumps(summary.programs)}; executions "
            f"{json.dumps(summary.runs)}")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return win, summary


def measure(cfg: dict, traffic: dict, seed: int, seconds: float,
            traced: bool, metric_names, compiles) -> dict:
    """Set-up, window and check of one run; returns the result's parts
    (everything but the device)."""
    run = harness.CellRun(cfg, traffic, seed)
    t = run.setup_times
    log(f"setup: load+warm {t['load_warm_s']:.3f} s, generator "
        f"{t['generator_s']:.3f} s")
    warm = int(traffic["warmup_batches"])
    for i in range(warm):
        t0 = time.perf_counter()
        run.warm_up(1)
        log(f"setup: warm-up step {i} {time.perf_counter() - t0:.3f} s")
    eng = run.cluster._jit
    log(f"setup: upload {eng.upload_s if eng else 0.0:.3f} s, compiles "
        f"{compiles.n} ({compiles.s:.3f} s, {compiles.cache_hits} from the "
        f"cache)")
    setup_s = time.perf_counter() - T_START
    log(f"setup: total {setup_s:.3f} s")

    n_compiles = compiles.n
    run.cluster.reset_stats()
    before = run.snapshot()
    if traced:
        win, summary = traced_window(run, TRACE_STEPS, seconds)
        rec = run.record(before, win, summary)
        metrics = harness.read_metrics(metric_names, rec)
        extra = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        out_breakdown = trace.breakdown(summary)
    else:
        win = run.window(seconds)
        rec = run.record(before, win)
        metrics = {"ops_per_s": {"value": win.ops_per_s(), "unit": "ops/s"},
                   "p99_ms": {"value": win.p99_ms(), "unit": "ms"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {k: v for k, v in metrics.items() if k in metric_names}
        extra, out_breakdown = {}, None
    step_s = [s.execute_s + s.merge_s for s in win.steps]
    log(f"window: {len(win.steps)} steps, {win.ops} ops in "
        f"{win.elapsed_s:.3f} s; step s min {min(step_s):.3f} median "
        f"{sorted(step_s)[len(step_s) // 2]:.3f} max {max(step_s):.3f}")
    log(f"window: compiles inside {compiles.n - n_compiles}; engine wall "
        f"{json.dumps({k: round(v, 4) for k, v in rec['engine_wall'].items()})}"
        f"; upload {rec['upload_s']:.3f} s; execute {rec['execute_s']:.3f} s;"
        f" merge {rec['merge_s']:.3f} s")
    log(f"window: counts {json.dumps(rec['counts'], sort_keys=True)}; "
        f"stats {json.dumps(rec['stats'], sort_keys=True)}")
    pool = run.cluster.pool
    backlog = sum(len(seg.sealed_entries()) - seg.merged_upto
                  for seg, _ in pool.merge_backlog)
    log(f"window: un-merged backlog {len(pool.merge_backlog)} segments, "
        f"{backlog} entries")
    peak = dev.peak_bytes()
    log(f"memory: peak_bytes_in_use {peak}; resident state peak "
        f"{eng.resident_bytes_peak if eng else 0}")

    t0 = time.perf_counter()
    checks, failed = run.check(win.steps)
    log(f"check: {time.perf_counter() - t0:.3f} s")
    attempted = sum(s.keys.shape[0] for s in win.steps)
    return {"correct": reference.verdict(checks), "attempted": attempted,
            "failed": failed, "metrics": metrics, "peak": peak,
            "extra": extra, "breakdown": out_breakdown, "checks": checks}


def main(argv=None) -> int:
    args = parse(argv)
    seed = args.seed % (1 << 64)
    bench = harness.benchmark()
    cell, cfg, traffic = harness.resolve(bench, args.workload)
    device = dev.tpu_or_exit(int(cell["chips"]))
    log(f"setup: compile cache {dev.place_compile_cache(harness.ROOT)}")
    compiles = dev.CompileCounter()
    kind = "per_layer" if args.trace else "end_to_end"
    names = [m["name"] for m in harness.cell_metrics(bench, cell["name"],
                                                    kind)]
    log(f"cell: {cell['name']} config {cell['config']} traffic "
        f"{cell['traffic']} seed {seed} seconds {args.seconds} trace "
        f"{args.trace}")
    r = measure(cfg, traffic, seed, args.seconds, bool(args.trace), names,
                compiles)
    device["memory_peak_bytes"] = r["peak"]
    device.update(r["extra"])
    out = {"correct": r["correct"], "attempted": r["attempted"],
           "failed": r["failed"], "metrics": r["metrics"], "device": device}
    if r["breakdown"] is not None:
        out["breakdown"] = r["breakdown"]
    out["checks"] = {k: {"value": v, "limit": reference.LIMITS[k]}
                     for k, v in r["checks"].items()}
    for k, v in r["checks"].items():
        log(f"check: {k} {v} (limit {reference.LIMITS[k]})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
