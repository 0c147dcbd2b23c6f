"""The trace reduction on a small synthetic XSpace: busy and idle over
the benchmark window, device time per program and per operation, and
idle gaps charged to the innermost benchmark span."""

import pytest
from jax.profiler import ProfileData

from bench.lib import trace


def _events(meta_ids, spans_ps):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {s} duration_ps: {d} }}"
        for m, (s, d) in zip(meta_ids, spans_ps))


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())


US = 1_000_000          # picoseconds per microsecond


def xspace(device_plane="/device:TPU:0", with_steps=True, dropped=0):
    """Host spans (timestamps in us): step 0-100 with execute 0-80 and
    merge 80-100; step 100-200 with execute 100-190, merge 190-200.
    Device programs: fused_window 10-40 and 50-70 (run ids differ),
    another program 120-150, and one before the window (-30..-10)."""
    host_names = {1: "bench.step", 2: "bench.execute_batch",
                  3: "bench.advance_merge", 4: "unrelated"}
    host = [(1, (0, 100)), (2, (0, 80)), (3, (80, 20)),
            (1, (100, 100)), (2, (100, 90)), (3, (190, 10)),
            (4, (0, 200))]
    if not with_steps:
        host = [h for h in host if h[0] != 1]
    dev_names = {1: "jit__fused_window_impl(11)",
                 2: "jit__fused_window_impl(12)", 3: "jit_other"}
    mods = [(1, (10, 30)), (2, (50, 20)), (3, (120, 30)), (3, (-30, 20))]
    ops = [(4, (10, 25)), (5, (50, 20)), (4, (120, 30))]
    op_names = {4: "%while.7", 5: "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p)"}
    stats = (f'stat_metadata {{ key: 9 value {{ id: 9 name: '
             f'"Trace Buffers Dropped" }} }} stats {{ metadata_id: 9 '
             f'uint64_value: {dropped} }}' if dropped else "")

    def body(items):
        return _events([m for m, _ in items],
                       [(s * US, d * US) for _, (s, d) in items])
    txt = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000 {body(host)} }}
  {_meta(host_names)} }}
planes {{ id: 2 name: "{device_plane}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000000 {body(mods)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000000 {body(ops)} }}
  {_meta({**dev_names, **op_names})} {stats} }}
"""
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))


def test_busy_idle_and_programs():
    s = trace.reduce_profile(xspace())
    assert s.window_s == pytest.approx(200e-6)
    assert s.busy_s == pytest.approx(80e-6)       # 30 + 20 + 30
    assert s.idle_share == pytest.approx(0.6)
    assert s.devices == 1
    assert s.programs == pytest.approx({"jit__fused_window_impl": 50e-6,
                                        "jit_other": 30e-6})
    assert s.ops == pytest.approx({"%while.7": 55e-6, "%fusion.3": 20e-6})


def test_executions_are_counted_inside_the_window():
    s = trace.reduce_profile(xspace())
    assert s.runs == {"jit__fused_window_impl": 2, "jit_other": 1}
    assert s.dropped == []
    assert trace.lost(s, "fused_window", 2) == ""


@pytest.mark.parametrize("dispatched,dropped,said,drops", [
    (3, 0, "2 of 3 fused_window executions recorded", ""),
    (2, 5, "", "/device:TPU:0: Trace Buffers Dropped 5"),
    (1, 5, "2 of 1 fused_window executions recorded",
     "/device:TPU:0: Trace Buffers Dropped 5")])
def test_a_trace_that_lost_events_says_what(dispatched, dropped, said,
                                            drops):
    s = trace.reduce_profile(xspace(dropped=dropped))
    assert trace.lost(s, "fused_window", dispatched) == said
    assert trace.dropped(s) == drops


def test_idle_gaps_go_to_the_innermost_span():
    s = trace.reduce_profile(xspace())
    # gaps: 0-10, 40-50, 70-80 (execute), 80-100 (merge), 100-120,
    # 150-190 (execute), 190-200 (merge)
    got = sorted((round(sec * 1e6), name) for name, sec in s.idle_gaps)
    assert got == sorted([
        (10, "bench.execute_batch"), (10, "bench.execute_batch"),
        (10, "bench.execute_batch"), (20, "bench.advance_merge"),
        (20, "bench.execute_batch"), (40, "bench.execute_batch"),
        (10, "bench.advance_merge")])
    assert s.idle_gaps[0] == ("bench.execute_batch", pytest.approx(40e-6))


def test_breakdown_lists_ops_then_gaps():
    b = trace.breakdown(trace.reduce_profile(xspace()))
    assert [n for n, _ in b["device_ops"]] == ["%while.7", "%fusion.3"]
    assert b["idle_gaps"][0][0] == "bench.execute_batch"
    assert len(b["idle_gaps"]) == 7


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="bench.step"):
        trace.reduce_profile(xspace(with_steps=False))
    with pytest.raises(ValueError, match="no device program"):
        trace.reduce_profile(xspace(device_plane="/host:metadata"))


@pytest.mark.parametrize("name,stable", [
    ("jit__fused_window_impl(123)", "jit__fused_window_impl"),
    ("jit_other", "jit_other")])
def test_program_name(name, stable):
    assert trace.program_name(name) == stable


def test_op_name_drops_the_hlo_text():
    assert trace.op_name("%fusion.3 = s32[8]{0} fusion(%p), kind=kLoop") \
        == "%fusion.3"
    assert trace.op_name("%while.7") == "%while.7"
