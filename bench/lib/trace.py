"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

Planes named ``/device:<platform>:<n>`` are devices.  On each, the line
``XLA Modules`` holds one event per program execution; its union over
the traced window is the device's busy time.  ``XLA Ops`` holds the
operations inside the programs.  Host planes hold the benchmark's own
``jax.profiler.TraceAnnotation`` spans, all named ``bench.<what>``;
the window runs from the first ``bench.step`` span's start to the last
one's end.  Each idle gap is cut where a benchmark span begins or ends,
and each piece is charged to the innermost span that held it.  Profiler
timestamps share one clock across host and device planes.

A trace can lose events: where a profiled step records more device
operations than the trace buffers hold (``fused_window`` records a set
per loop iteration), whole program executions can vanish and the device
then looks idle.  So the summary counts the executions of each program
in the window; ``lost`` compares the count with what the host
dispatched.  A plane statistic that reports dropped events
(``dropped``) can also come with every execution in the trace, the
drops then being operations inside the programs: that leaves the
program times and the busy union whole and only the breakdown's
operations partial.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.step"
_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                  # averaged over the devices with events
    devices: int
    programs: dict = field(default_factory=dict)   # name -> device s
    runs: dict = field(default_factory=dict)       # name -> executions
    dropped: list = field(default_factory=list)    # [(plane, stat, value)]
    ops: dict = field(default_factory=dict)        # name -> device s
    idle_gaps: list = field(default_factory=list)  # [(span, s)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def newest_xspace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def program_name(event_name: str) -> str:
    """A program's stable name: the module name without its run id."""
    return _PROGRAM_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """An operation's name without the HLO text that can follow it
    (``%fusion.3 = s32[...] fusion(...)`` -> ``%fusion.3``)."""
    return event_name.split(" = ", 1)[0][:120]


def _union(intervals):
    """Merge [start, end) intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_profile(profile, top: int = 10) -> TraceSummary:
    """``profile``: a ``jax.profiler.ProfileData``.  Raises ValueError
    when the trace holds no benchmark window or no device event in it."""
    spans = []                       # (start, end, name)
    device_modules = {}              # plane -> [(start, end, name)]
    dropped = []                     # (plane, stat, value)
    for plane in profile.planes:
        is_device = bool(_DEVICE_PLANE.match(plane.name))
        dropped.extend((plane.name, k, v) for k, v in plane.stats
                       if "drop" in k.lower() and v)
        for line in plane.lines:
            if is_device and line.name == MODULE_LINE:
                device_modules.setdefault(plane.name, []).extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events)
            elif not is_device:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    steps = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not steps:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo = min(s for s, _ in steps)
    hi = max(e for _, e in steps)
    busy_by_dev = {}
    programs: dict = {}
    runs: dict = {}
    for dev, evs in device_modules.items():
        inside = [(s, e, n) for s, e, n in evs if e > lo and s < hi]
        if not inside:
            continue
        merged = _union(_clip([(s, e) for s, e, _ in inside], lo, hi))
        busy_by_dev[dev] = merged
        for s, e, n in inside:
            nm = program_name(n)
            programs[nm] = programs.get(nm, 0.0) + \
                (min(e, hi) - max(s, lo)) * 1e-9
            runs[nm] = runs.get(nm, 0) + 1
    if not busy_by_dev:
        raise ValueError("no device program ran inside the traced window")
    # a second pass sums the operations (millions of events per step
    # where a program loops) without holding them
    ops_ns: dict = {}                # event name -> ns inside the window
    for plane in profile.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            for e in line.events:
                s, t = e.start_ns, e.end_ns
                if t > lo and s < hi:
                    n = e.name
                    ops_ns[n] = ops_ns.get(n, 0) + min(t, hi) - max(s, lo)
    ops: dict = {}
    for n, ns in ops_ns.items():
        nm = op_name(n)
        ops[nm] = ops.get(nm, 0.0) + ns * 1e-9
    busy_s = sum(sum(e - s for s, e in m) for m in busy_by_dev.values()) \
        / len(busy_by_dev) * 1e-9
    # idle gaps on the first device, charged to the innermost (the
    # shortest) span that held them
    merged = busy_by_dev[min(busy_by_dev)]
    gaps = []
    prev = lo
    for s, e in merged + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for s, e in gaps:
        # cut the gap where a span begins or ends: each piece has one
        # innermost span
        cuts = sorted({s, e} | {t for ss, se, _ in spans
                                for t in (ss, se) if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            holders = [(se - ss, n) for ss, se, n in spans
                       if ss <= mid < se]
            named.append((min(holders)[1] if holders
                          else "(no bench span)", (b - a) * 1e-9))
    named.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_s, devices=len(busy_by_dev),
        programs=programs, runs=runs, dropped=dropped,
        ops=dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top]),
        idle_gaps=named[:top])


def lost(summary: TraceSummary, program: str, dispatched: int) -> str:
    """What the trace lost of the window: '' where it holds all of the
    ``dispatched`` executions of the programs whose names contain
    ``program``."""
    ran = sum(n for name, n in summary.runs.items() if program in name)
    if ran != dispatched:
        return f"{ran} of {dispatched} {program} executions recorded"
    return ""


def dropped(summary: TraceSummary) -> str:
    """Every plane statistic that reports dropped events, or ''."""
    return "; ".join(f"{plane}: {stat} {value}"
                     for plane, stat, value in summary.dropped)


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the heaviest device operations
    (programs where the trace names no operations) and the longest idle
    gaps, as [name, seconds] pairs."""
    heavy = summary.ops or summary.programs
    return {
        "device_ops": [[n, s] for n, s in
                       sorted(heavy.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:top]],
    }
