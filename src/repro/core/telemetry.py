"""Per-cluster spans and counters at the store's layer boundaries.

One :class:`Telemetry` belongs to one cluster (``DinomoCluster.telemetry``)
and is handed to its pool, the pool's index and the jit driver, so two
clusters in one process never share a number.  Two kinds of record:

* ``span(name, **ids)`` times a region: per name, the total seconds,
  the self seconds (total less the spans opened inside it) and the
  call count.  Each span is also a ``jax.profiler.TraceAnnotation``
  named ``dinomo.<name>``, so under a profiler it lands in the trace on
  the device planes' clock, with ``batch=<the cluster's batch seq>``
  and the span's own ids (``kn=<name>``) as arguments.  With no trace
  active it costs a ``perf_counter`` pair and the annotation object.
* ``counts``: exact event counts (``dispatches``, ``device_ops``,
  ``host_ops.<reason>``, ``jit.upload_bytes``, ``jit.sync_bytes``,
  ``plan.*``, ``merge_plan.*``).

Spans sit at layer boundaries only, never inside a per-op loop.  They
stay in memory; ``snapshot()`` gives plain dicts a reader subtracts.

``ENGINE_WALL`` is the process-wide sum of the seven engine spans of
every live telemetry under the names of the module dict they replaced
(``transition.ENGINE_WALL``): the accepted benchmark harness
(``bench/lib/harness.py``) still reads them there, and
``JitEngine.upload_s`` likewise.  It is a read-only view with no clock
of its own.
"""

from __future__ import annotations

import weakref
from collections import Counter
from collections.abc import Mapping
from time import perf_counter

from jax.profiler import TraceAnnotation

PREFIX = "dinomo."

#: the engine spans under the old ``ENGINE_WALL`` keys
ENGINE_SPANS = {"host_plan": "host.plan", "host_apply": "host.apply",
                "host_replay": "host.replay", "jit_prep": "jit.prep",
                "jit_dispatch": "jit.dispatch", "jit_fold": "jit.fold",
                "jit_sync": "jit.sync"}

_LIVE: weakref.WeakSet = weakref.WeakSet()


class _Span:
    __slots__ = ("tel", "name", "ann", "t0")

    def __init__(self, tel, name, ann):
        self.tel, self.name, self.ann = tel, name, ann

    def __enter__(self):
        self.ann.__enter__()
        self.tel._open.append(0.0)
        self.t0 = perf_counter()

    def __exit__(self, *exc):
        dt = perf_counter() - self.t0
        tel = self.tel
        inner = tel._open.pop()
        if tel._open:
            tel._open[-1] += dt
        rec = tel.spans.get(self.name)
        if rec is None:
            rec = tel.spans[self.name] = [0.0, 0.0, 0]
        rec[0] += dt
        rec[1] += dt - inner
        rec[2] += 1
        self.ann.__exit__(*exc)


class Telemetry:
    """One cluster's spans and counters (see the module docstring)."""

    def __init__(self):
        self.counts: Counter = Counter()
        self.spans: dict[str, list] = {}     # name -> [total, self, calls]
        self.batch = 0                       # the cluster's batch seq
        self._open: list[float] = []         # child seconds per open span
        _LIVE.add(self)

    def span(self, name: str, **ids) -> _Span:
        return _Span(self, name,
                     TraceAnnotation(PREFIX + name, batch=self.batch, **ids))

    def total(self, name: str) -> float:
        rec = self.spans.get(name)
        return rec[0] if rec is not None else 0.0

    def snapshot(self) -> dict:
        return {"spans": {n: {"total_s": r[0], "self_s": r[1],
                              "calls": r[2]}
                          for n, r in self.spans.items()},
                "counts": dict(self.counts)}

    def reset(self) -> None:
        self.counts.clear()
        self.spans.clear()


class _EngineWall(Mapping):
    """Read-only: each old ``ENGINE_WALL`` key's span total, summed over
    every live :class:`Telemetry` in the process."""

    def __getitem__(self, key: str) -> float:
        span = ENGINE_SPANS[key]
        return sum(t.total(span) for t in list(_LIVE))

    def __iter__(self):
        return iter(ENGINE_SPANS)

    def __len__(self) -> int:
        return len(ENGINE_SPANS)


ENGINE_WALL = _EngineWall()
