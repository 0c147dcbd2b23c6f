#!/usr/bin/env python3
"""The control of the benchmark's check, on the chip at a cell's size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The control is the store's own path that answers no read
(``execute_batch(..., collect_values=False)``): the shortcut a change
could take to drop the per-read value fold from the timed path.  One
process loads the cell's cluster once, then runs the cell's warm-up and
a window of ``--seconds`` per seed with that path switched on, and
prints the numbers the check compares for each seed's window; every
seed has to fail the check.  The benchmark's own runs never run this.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.lib import device as dev  # noqa: E402
from bench.lib import harness, reference  # noqa: E402
from bench.lib.harness import log  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell, cfg, traffic = harness.resolve(bench, args.workload)
    device = dev.tpu_or_exit(int(cell["chips"]))
    dev.place_compile_cache(harness.ROOT)
    t0 = time.perf_counter()
    run = harness.CellRun(cfg, traffic, args.seeds[0], collect_values=False)
    run.warm_up(int(traffic["warmup_batches"]))
    log(f"control: set-up {time.perf_counter() - t0:.1f} s")
    readings = []
    for seed in args.seeds:
        run.workload = harness.make_workload(traffic, cfg, seed % (1 << 64))
        run.warm_up(int(traffic["warmup_batches"]))
        win = run.window(args.seconds)
        checks, failed = run.check(win.steps)
        reads = sum(int((s.kinds == 0).sum()) for s in win.steps)
        row = {"seed": seed, "window_ops": win.ops, "window_reads": reads,
               "window_failed": failed, "checks": checks,
               "correct": reference.verdict(checks)}
        log(f"control: {json.dumps(row)}")
        readings.append(row)
    print(json.dumps({"control": args.workload, "device": device,
                      "readings": readings}), flush=True)
    return 0 if not any(r["correct"] for r in readings) else 1


if __name__ == "__main__":
    sys.exit(main())
