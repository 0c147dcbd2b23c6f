"""The harness off the chip: the command refuses the CPU, every name in
``BENCHMARK.json`` resolves to a file, and the step loop, the metric
arithmetic and the check run at a tiny size -- where the check passes
the store as it is and fails it with its timed path broken."""

import json
import os
import re
import subprocess

import numpy as np
import pytest

from bench import run as bench_run
from bench.lib import device, harness, reference, trace
from bench.lib.harness import CellRun, Step, Window

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TINY_KEYS = 4096
TINY_BATCH = 1024


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def tiny(bench, cell_name, traffic_name=None):
    """The cell's configuration and traffic, cut to a size a CPU
    runs in seconds; every other field is the cell's own."""
    cell, cfg, traffic = harness.resolve(bench, cell_name)
    if traffic_name is not None:
        traffic = harness.load_json(harness.traffic_path(traffic_name))
    cfg = dict(cfg, num_keys=TINY_KEYS,
               kn_cache_bytes=TINY_KEYS * cfg["value_bytes"] // 32,
               clht_buckets=1 << 17)
    return cfg, dict(traffic, batch_ops=TINY_BATCH)


# ----- the command ---------------------------------------------------------
def test_command_refuses_the_cpu(bench):
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(bench["command"] + [
        "--workload", cell, "--seed", str(2 ** 31 + 3), "--seconds", "1",
        "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"metrics"' not in r.stdout


# ----- files found by name -------------------------------------------------
def test_every_name_resolves_to_a_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["config"] in configs
        assert os.path.isfile(harness.config_path(cell["config"]))
        assert os.path.isfile(harness.traffic_path(cell["traffic"]))
    for c in bench["configs"]:
        assert c["file"] == os.path.relpath(harness.config_path(c["name"]),
                                            ROOT)
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in bench["per_layer"]:
        mod = harness.load_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


def test_names_units_and_paths_keep_to_the_contract(bench):
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in bench["configs"] + bench["workloads"]
             + metrics]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for cell in bench["workloads"]:
        assert harness.cell_metrics(bench, cell["name"], "per_layer")


# ----- traffic ---------------------------------------------------------------
def test_traffic_is_fixed_by_the_seed(bench):
    cfg, traffic = tiny(bench, "z099-ycsbc")
    a, b = (harness.make_workload(traffic, cfg, 7) for _ in range(2))
    for _ in range(3):
        ka, ya = a.ops_arrays(TINY_BATCH)
        kb, yb = b.ops_arrays(TINY_BATCH)
        np.testing.assert_array_equal(ya, yb)
        assert ka.shape == (TINY_BATCH,) and not ka.any()
    other = harness.make_workload(traffic, cfg, 8).ops_arrays(TINY_BATCH)
    assert not np.array_equal(other[1], ya)
    with pytest.raises(ValueError, match="loop"):
        harness.make_workload(dict(traffic, loop="open"), cfg, 7)


# ----- the window's arithmetic -----------------------------------------------
def _step(n, lat, executed=None):
    z = np.zeros(n, np.uint8)
    return Step(z, z.astype(np.int64), 0, None,
                n if executed is None else executed, lat, 0.0,
                np.full(n, lat))


def test_rate_counts_every_executed_op_over_the_elapsed_window():
    win = Window([_step(100, 0.5), _step(100, 0.7, executed=60)], 2.0)
    assert win.ops == 160
    assert win.ops_per_s() == pytest.approx(80.0)


def test_p99_is_over_ops_not_steps():
    # 99 fast steps of 100 ops and one slow step of 100 ops: the slow
    # step holds 1% of the ops, so p99 lies between the two latencies
    win = Window([_step(100, 0.1)] * 99 + [_step(100, 2.0)], 30.0)
    assert 100.0 < win.p99_ms() < 2000.0
    win = Window([_step(100, 0.1)] * 49 + [_step(100, 2.0)], 30.0)
    assert win.p99_ms() == pytest.approx(2000.0)


# ----- the loop and the check at a tiny size ---------------------------------
@pytest.fixture(scope="module")
def z099_run(bench):
    cfg, traffic = tiny(bench, "z099-ycsbc")
    run = CellRun(cfg, traffic, 2 ** 33 + 1)
    run.warm_up(1)
    run.cluster.reset_stats()
    before = run.snapshot()
    win = run.window(60.0, max_steps=3)
    return run, before, win


def test_loop_runs_on_the_device_path_and_checks_out(bench, z099_run):
    run, before, win = z099_run
    assert len(win.steps) == 3 and win.ops == 3 * TINY_BATCH
    rec = run.record(before, win)
    assert rec["counts"]["device_ops"] > 0
    assert rec["stats"]["ops"] == 3 * TINY_BATCH
    names = [m["name"] for m in harness.cell_metrics(bench, "z099-ycsbc",
                                                     "per_layer")]
    got = harness.read_metrics(names, rec)
    # the device metrics need a trace; every host metric is read
    assert set(got) == set(names) - {"fused_window_us_per_op",
                                     "device_idle_share"}
    parts = (got["cluster_self_us_per_op"]["value"]
             + got["host_engine_us_per_op"]["value"]
             + got["jit_residency_us_per_op"]["value"]
             + got["jit_prep_fold_us_per_op"]["value"])
    assert parts <= rec["execute_s"] / rec["ops"] * 1e6 * 1.0001
    checks, failed = run.check(win.steps)
    assert checks == dict.fromkeys(reference.LIMITS, 0) and failed == 0


DROPS = (("/device:TPU:0", "Trace Buffers Dropped", 3),)


@pytest.mark.parametrize("recorded,dropped,kept", [
    (0, (), True), (-1, (), False), (0, DROPS, True), (-1, DROPS, False),
    (1, (), False)])
def test_device_metrics_need_every_dispatch_in_the_trace(
        bench, z099_run, recorded, dropped, kept):
    """A trace that lost executions of the executor gives no device
    metric; dropped events with every execution recorded leave them."""
    run, before, win = z099_run
    dispatched = run.record(before, win)["counts"]["dispatches"]
    assert dispatched >= 3
    summary = trace.TraceSummary(
        window_s=1.0, busy_s=0.75, devices=1,
        programs={"jit__fused_window_impl": 0.5, "jit_other": 0.25},
        runs={"jit__fused_window_impl": dispatched + recorded,
              "jit_other": 1},
        dropped=list(dropped))
    rec = run.record(before, win, summary)
    assert (rec["trace"] is summary) is kept
    assert bool(rec["trace_lost"]) is not kept
    got = harness.read_metrics(["fused_window_us_per_op",
                                "device_idle_share"], rec)
    if kept:
        assert got["device_idle_share"]["value"] == pytest.approx(25.0)
        assert got["fused_window_us_per_op"]["value"] == pytest.approx(
            0.5 / rec["counts"]["device_ops"] * 1e6)
    else:
        assert got == {}


def test_reads_and_writes_check_out(bench):
    cfg, traffic = tiny(bench, "z099-ycsbc", "ycsb-a")
    run = CellRun(cfg, traffic, 11)
    run.warm_up(2)
    checks, _ = run.check()
    assert checks == dict.fromkeys(reference.LIMITS, 0)
    assert sum(int(s.kinds.sum()) for s in run.history) > 0


# ----- the check fails a broken timed path -----------------------------------
def _wrap_execute(monkeypatch, fault):
    core, _ = harness.import_store()
    real = core.DinomoCluster.execute_batch

    def broken(self, kinds, keys, **kw):
        return fault(real, self, np.asarray(kinds), np.asarray(keys), kw)
    monkeypatch.setattr(core.DinomoCluster, "execute_batch", broken)


def answer_altered(real, self, kinds, keys, kw):
    res = real(self, kinds, keys, **kw)
    i = int(np.nonzero(kinds == 0)[0][-1])
    res.values[i] = "v-altered"
    return res


def half_left_out(real, self, kinds, keys, kw):
    h = keys.size // 2
    res = real(self, kinds[:h], keys[:h], **kw)
    res.values = res.values + [None] * (keys.size - h)
    return res


def writes_dropped(real, self, kinds, keys, kw):
    """The step leaves the store's state as it was: no write lands,
    while every op is reported executed."""
    res = real(self, np.zeros_like(kinds), keys, **kw)
    for i in np.nonzero(kinds)[0].tolist():
        res.values[i] = None
    return res


@pytest.mark.parametrize("traffic_name,fault,broken", [
    ("ycsb-c", answer_altered, {"read_mismatch"}),
    ("ycsb-c", half_left_out, {"read_mismatch", "unexecuted"}),
    ("ycsb-a", answer_altered, {"read_mismatch"}),
    ("ycsb-a", half_left_out, {"read_mismatch", "unexecuted",
                               "readback_mismatch"}),
    ("ycsb-a", writes_dropped, {"read_mismatch", "readback_mismatch"}),
])
def test_a_run_with_a_broken_timed_path_is_not_correct(
        bench, monkeypatch, traffic_name, fault, broken):
    """The run as the command drives it, minus the look for a chip."""
    cfg, traffic = tiny(bench, "z099-ycsbc", traffic_name)
    _wrap_execute(monkeypatch, fault)
    names = [m["name"] for m in bench["end_to_end"]]
    r = bench_run.measure(cfg, traffic, 2 ** 31 + 9, 0.5, False, names,
                          device.CompileCounter())
    assert r["correct"] is False and r["failed"] > 0
    assert {k for k, v in r["checks"].items() if v} == broken
    assert set(r["metrics"]) == set(names)


def test_the_control_is_not_correct(bench):
    """The control: the store's own path that answers no read
    (``collect_values=False``), at the cell's traffic."""
    cfg, traffic = tiny(bench, "z05-ycsbc")
    run = CellRun(cfg, traffic, 5, collect_values=False)
    run.warm_up(1)
    checks, _ = run.check()
    assert checks["read_mismatch"] == TINY_BATCH
    assert not reference.verdict(checks)


def test_the_result_line_names_the_check_last(bench, monkeypatch, capsys):
    """``main`` with the look for a chip answered by the CPU: the
    result line carries the device and ends with each number compared
    beside its limit."""
    cfg, traffic = tiny(bench, "z099-ycsbc")
    monkeypatch.setattr(harness, "resolve",
                        lambda b, n: ({"name": n, "config": "c",
                                       "traffic": "t", "chips": 1},
                                      cfg, traffic))
    monkeypatch.setattr(device, "tpu_or_exit",
                        lambda chips: {"platform": "cpu", "kind": "cpu",
                                       "count": 1})
    monkeypatch.setattr(device, "place_compile_cache", lambda root: "-")
    assert bench_run.main(["--workload", "z099-ycsbc", "--seed",
                           str(2 ** 32 + 1), "--seconds", "0.5",
                           "--trace", "0"]) == 0
    cap = capsys.readouterr()
    out = json.loads(cap.out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["checks"] == {k: {"value": 0, "limit": v}
                             for k, v in reference.LIMITS.items()}
    assert set(out["metrics"]) == {"ops_per_s", "p99_ms", "setup_s"}
    assert cap.err.strip().splitlines()[-1].startswith("check: ")
