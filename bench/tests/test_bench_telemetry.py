"""What the benchmark reads of the store's own telemetry: the residency
byte count, exact at a tiny size on the CPU, and the executor's program
name that the trace reduction matches."""

import numpy as np
import pytest

from bench.lib import harness
from bench.lib.harness import CellRun
from bench.tests.test_bench_harness import (  # noqa: F401 (a fixture)
    TINY_BATCH, bench, tiny)

RESIDENCY = "residency_bytes_per_op"


def _record(ops, **counts):
    return {"ops": ops, "counts": counts}


def test_residency_reads_upload_and_sync_bytes_over_ops():
    got = harness.read_metrics([RESIDENCY], _record(
        8192, dispatches=4, **{"jit.upload_bytes": 3 << 20,
                               "jit.sync_bytes": 1 << 20}))
    assert got == {RESIDENCY: {"value": (4 << 20) / 8192, "unit": "B/op"}}


@pytest.mark.parametrize("record", [
    _record(8192, dispatches=4, device_ops=8192),    # a store without it
    _record(0, **{"jit.upload_bytes": 0, "jit.sync_bytes": 0}),
])
def test_residency_reads_nothing_where_there_is_nothing(record):
    assert harness.read_metrics([RESIDENCY], record) == {}


def _state_bytes(cache) -> int:
    """One KN's executor state: six per-slot int32 arrays padded to a
    power of two, the count histogram and the registers."""
    from repro.kernels import batch_executor as be
    pad = 1 << (int(cache.kind.shape[0]) - 1).bit_length()
    return 4 * (6 * pad + len(cache._cnt_hist) + be.NUM_REGS)


def test_residency_is_each_kn_state_up_and_back_a_step(bench):  # noqa: F811
    cfg, traffic = tiny(bench, "z099-ycsbc")
    run = CellRun(cfg, traffic, 2 ** 34 + 5)
    run.warm_up(1)
    before = run.snapshot()
    win = run.window(60.0, max_steps=2)
    rec = run.record(before, win)
    counts = rec["counts"]
    assert not any(v for k, v in counts.items()
                   if k.startswith("host_ops."))
    kns = run.cluster.kns.values()
    step = sum(_state_bytes(kn.cache) for kn in kns)
    assert counts["jit.upload_bytes"] == counts["jit.sync_bytes"] \
        == len(win.steps) * step
    got = harness.read_metrics([RESIDENCY], rec)[RESIDENCY]["value"]
    assert got == 2 * len(win.steps) * step / (len(win.steps) * TINY_BATCH)


def test_the_executor_program_is_named_as_the_harness_matches_it():
    """The trace's ``XLA Modules`` events carry the HLO module's name;
    ``harness.EXECUTOR`` must stay a part of it."""
    import jax.numpy as jnp
    from repro.kernels import batch_executor as be
    from repro.kernels.batch_executor.ops import compiled_window
    state = tuple(np.zeros(64, np.int32) for _ in range(6)) + (
        np.zeros(be.CNT_HIST_MAX + 1, np.int32),
        np.zeros(be.NUM_REGS, np.int32))
    w = np.zeros(128, np.int32)
    lowered = compiled_window("cpu").lower(
        state, w, w, w, w, w, w, jnp.int32(0), jnp.int32(64),
        jnp.int32(1024), jnp.zeros(be.TABLE_N, jnp.int32))
    module = lowered.compile().as_text().split("\n", 1)[0]
    assert module.startswith("HloModule ")
    assert harness.EXECUTOR in module.split()[1]
