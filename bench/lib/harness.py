"""The benchmark's machinery: files found by name, the cell's cluster,
its traffic, the step loop, the metrics and the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.  A
new cell needs those files and an entry in ``BENCHMARK.json``, never a
change here.

One step of the loop is ``execute_batch(kinds, keys, values=...,
collect_values=True, engine="jit")`` over the step's ops followed by
``advance_merge(merge_per_op * ops)``, the store's background merge.
An op's latency runs from the step's start (the end of the previous
step, merge included) to the return of its batch.  The loop is closed:
the next step starts when the last one, merge included, has returned.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from . import reference, trace
from .reference import ReferenceStore, write_value
from .ycsb import Workload

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SPAN_STEP = "bench.step"
SPAN_EXECUTE = "bench.execute_batch"
SPAN_MERGE = "bench.advance_merge"
EXECUTOR = "fused_window"       # the device program of each jit dispatch


def log(msg: str) -> None:
    """Everything but the result goes to standard error."""
    print(msg, file=sys.stderr, flush=True)


# ----- files found by name ------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{name}.py")


def load_metric(name: str):
    """A per-layer metric's module: ``LAYER``, ``UNIT``, ``MOVES`` and
    ``read(record)``, which returns the value or None when the record
    holds nothing to read."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", metric_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, cell_name: str):
    """(cell, config, traffic) for a cell named in BENCHMARK.json."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"bench: no cell {cell_name!r} in BENCHMARK.json "
                         f"(cells: {', '.join(sorted(cells))})")
    cell = cells[cell_name]
    return (cell, load_json(config_path(cell["config"])),
            load_json(traffic_path(cell["traffic"])))


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", (cell_name,))]


# ----- the store under test -----------------------------------------------
def import_store():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core as core
    from repro.core import transition
    return core, transition.ENGINE_WALL


def build_cluster(cfg: dict):
    """The configuration's cluster, loaded and warmed by the store's
    own ``load(warm=True)``."""
    core, _ = import_store()
    c = core.DinomoCluster(
        core.VARIANTS[cfg["variant"]], num_kns=cfg["num_kns"],
        cache_bytes=cfg["kn_cache_bytes"], value_bytes=cfg["value_bytes"],
        num_buckets=cfg["clht_buckets"],
        segment_capacity=cfg["segment_capacity"], seed=0)
    c.load(((k, reference.loaded_value(k))
            for k in range(cfg["num_keys"])), warm=True)
    return c


# ----- traffic ------------------------------------------------------------
def make_workload(traffic: dict, cfg: dict, seed: int) -> Workload:
    """The cell's op stream from the seed (a zipfian is YCSB's scrambled
    one)."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return Workload(cfg["num_keys"], cfg["zipf"],
                    (traffic["read"], traffic["update"], traffic["insert"]),
                    seed, distribution=traffic["distribution"])


# ----- the run --------------------------------------------------------------
@dataclass
class Step:
    kinds: np.ndarray
    keys: np.ndarray
    base: int                      # global position of the first op
    answers: list | None
    executed: int
    execute_s: float
    merge_s: float
    latency_s: np.ndarray          # per op


@dataclass
class Window:
    steps: list = field(default_factory=list)
    elapsed_s: float = 0.0

    @property
    def ops(self) -> int:
        return sum(s.executed for s in self.steps)

    def ops_per_s(self) -> float:
        return self.ops / self.elapsed_s

    def p99_ms(self) -> float:
        lat = np.concatenate([s.latency_s for s in self.steps])
        return float(np.percentile(lat, 99)) * 1e3


class CellRun:
    """One cell's cluster, traffic and reference bookkeeping.

    ``collect_values=False`` runs the store's own path that answers no
    read: the control, which the check must refuse."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 collect_values: bool = True):
        self.cfg = cfg
        self.collect_values = collect_values
        self.merge_per_op = int(cfg["merge_per_op"])
        self.history: list[Step] = []       # warm-up and window, in order
        self.next_op = 0
        self.setup_times: dict[str, float] = {}
        t0 = time.perf_counter()
        self.cluster = build_cluster(cfg)
        self.setup_times["load_warm_s"] = time.perf_counter() - t0
        self.batch = int(traffic["batch_ops"])
        t0 = time.perf_counter()
        self.workload = make_workload(traffic, cfg, seed)
        self.setup_times["generator_s"] = time.perf_counter() - t0
        _, self.engine_wall = import_store()

    # ----- one step ---------------------------------------------------------
    def execute(self, kinds, keys, start: float) -> Step:
        """One step over the given ops; ``start`` is the step's start
        on the ``time.perf_counter`` clock."""
        from jax.profiler import TraceAnnotation
        base = self.next_op
        self.next_op += keys.shape[0]
        t0 = time.perf_counter()
        with TraceAnnotation(SPAN_EXECUTE):
            res = self.cluster.execute_batch(
                kinds, keys, values=lambda j: write_value(base + j),
                collect_values=self.collect_values, engine="jit")
        t1 = time.perf_counter()
        with TraceAnnotation(SPAN_MERGE):
            self.cluster.advance_merge(self.merge_per_op * keys.shape[0])
        t2 = time.perf_counter()
        step = Step(kinds, keys, base, res.values, res.executed, t1 - t0,
                    t2 - t1, np.full(keys.shape[0], t1 - start))
        self.history.append(step)
        return step

    def warm_up(self, batches: int) -> None:
        for _ in range(batches):
            kinds, keys = self.workload.ops_arrays(self.batch)
            self.execute(kinds, keys, time.perf_counter())

    def window(self, seconds: float, max_steps: int | None = None) -> Window:
        """Steps until ``seconds`` have passed (the step in flight then
        finishes and counts) or ``max_steps`` steps have run."""
        from jax.profiler import TraceAnnotation
        win = Window()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        start = t0
        while time.perf_counter() < deadline:
            if max_steps is not None and len(win.steps) >= max_steps:
                break
            with TraceAnnotation(SPAN_STEP):
                kinds, keys = self.workload.ops_arrays(self.batch)
                win.steps.append(self.execute(kinds, keys, start))
            start = time.perf_counter()
        win.elapsed_s = time.perf_counter() - t0
        return win

    # ----- what the layers did --------------------------------------------
    def snapshot(self) -> dict:
        eng = self.cluster._jit
        return {"engine_wall": dict(self.engine_wall),
                "counts": dict(eng.counts) if eng is not None else {},
                "upload_s": eng.upload_s if eng is not None else 0.0}

    def record(self, before: dict, win: Window, summary=None) -> dict:
        """The raw record that the per-layer metrics read: host spans,
        deltas of the store's counters and clocks over the window, the
        cache statistics since the window began, and the trace's
        summary.  The summary is left out (None) where the trace lost
        executions of the executor, which then go under ``trace_lost``,
        so no device metric is read from it.  Dropped events with every
        execution recorded were operations inside the programs: only
        the breakdown's operations are partial, and stderr says so."""
        after = self.snapshot()
        counts = {k: v - before["counts"].get(k, 0)
                  for k, v in after["counts"].items()}
        lost = "" if summary is None else trace.lost(
            summary, EXECUTOR, counts.get("dispatches", 0))
        if lost:
            log(f"trace: incomplete, device metrics left out: {lost}")
        drops = "" if summary is None else trace.dropped(summary)
        if drops and not lost:
            log(f"trace: events dropped ({drops}), every {EXECUTOR} "
                f"execution recorded: device metrics kept, the "
                f"breakdown's operations partial")
        return {
            "ops": win.ops,
            "steps": len(win.steps),
            "elapsed_s": win.elapsed_s,
            "execute_s": sum(s.execute_s for s in win.steps),
            "merge_s": sum(s.merge_s for s in win.steps),
            "engine_wall": {k: after["engine_wall"][k]
                            - before["engine_wall"].get(k, 0.0)
                            for k in after["engine_wall"]},
            "counts": counts,
            "upload_s": after["upload_s"] - before["upload_s"],
            "stats": self.cluster.aggregate_stats(),
            "trace": None if lost else summary,
            "trace_lost": lost,
        }

    # ----- the check -------------------------------------------------------
    def check(self, window_steps=()) -> tuple[dict, int]:
        """Every number compared against the reference (limits in
        ``reference.LIMITS``): each read answered in every step, every
        op executed, every touched key read back through the index once
        all merges have landed, and the pool's integrity.  Also returns
        the ops of ``window_steps`` that were not executed or answered
        wrongly."""
        ref = ReferenceStore(self.cfg["num_keys"])
        checks = dict.fromkeys(reference.LIMITS, 0)
        in_window = {id(s) for s in window_steps}
        failed = 0
        for s in self.history:
            bad = ref.replay(s.kinds, s.keys, s.base, s.answers)
            lost = s.keys.shape[0] - s.executed
            checks["read_mismatch"] += bad
            checks["unexecuted"] += lost
            if id(s) in in_window:
                failed += bad + lost
        pool = self.cluster.pool
        pool.merge_all()
        touched = np.unique(np.concatenate([s.keys for s in self.history]))
        ptrs, _ = pool.index_lookup_batch(touched)
        heap = pool.heap_val
        got = [heap[p] if p >= 0 else None for p in ptrs.tolist()]
        checks["readback_mismatch"] = ref.readback_mismatch(touched, got)
        checks["integrity"] = len(pool.verify_integrity())
        return checks, failed


def read_metrics(names, record: dict) -> dict:
    """Each per-layer metric the record holds something for."""
    out = {}
    for name in names:
        mod = load_metric(name)
        v = mod.read(record)
        if v is not None:
            out[name] = {"value": float(v), "unit": mod.UNIT}
    return out
