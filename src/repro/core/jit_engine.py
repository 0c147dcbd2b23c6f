"""Compiled batch engine driver: ``execute_batch(engine="jit")``.

Runs each KN window of the batched data plane through the jitted fused
executor (``repro.kernels.batch_executor``) instead of the host
planner: the window's DAC transitions -- value/shortcut hits, Eq. 1
promotions with the full make-space loop, prefetch-resolved misses,
staged write fills -- execute as one device dispatch over
device-resident per-key state, and the host only folds the outcome
(stats, RT sums, the miss-EMA refold in op order, segment-cache puts,
collected read values) from the returned per-op event records.

Residency model
---------------
A KN's cache state (kind/count/stamp/length/ptr/histogram/registers
plus a wrote-this-batch flag) is uploaded once per batch on first use
and stays device-resident across that KN's windows; the returned state
of each dispatch feeds the next (donated buffers on accelerators).  It
is scattered back to the host cache arrays whenever the host must
touch the cache:

  * a truncation cut (the residual replays through the host engine),
  * a host-run span (deletes, short segments, degenerate progress),
  * a replicated-key op or batch end (``sync_all``).

Scatter-back rewrites the dense arrays and re-seeds the cache's *lazy*
LRU/LFU heaps with one record per entry whose kind changed on device;
entries whose kind survived keep their existing records, which the
lazy pop discipline self-heals (stale stamp/count records refresh on
pop).  The engine is decision-for-decision identical to the host
engine -- property-tested over the full sweep configs in
tests/test_dataplane.py / test_writeplane.py.

Truncation -> replay contract
-----------------------------
The device machine stops *before* the first op it cannot prove
on-device (segcache-backed or unprefetched reads, histogram spill,
EMA-staled or table-overflow promote decisions; see
``kernels.batch_executor.ref``) and reports how far it got plus a
reason code.  The driver scatters back, replays a short residual
(including the blocking op) through the host engine's exact per-op
machinery, and resumes on device.  Deletes are statically clamped:
the dispatch never spans one.  Degenerate progress (repeated cuts
with little forward motion) falls back to the host engine for the
rest of the window.

Everything here is int32 on device; the upload guards check the
actual ranges (clock, counts, heap pointers, capacity) and fall back
to the host engine when any could overflow.  The Eq. 1 float
comparison is discretized host-side into an integer threshold table
(rebuilt whenever the miss-RT EMA moves), so no float arithmetic runs
on device.
"""

from __future__ import annotations

import heapq

import numpy as np

from . import sanitize

_I31 = 2 ** 31 - 1
_GUARD = 2 ** 30          # headroom for clocks/counts that grow per op

#: spans shorter than this never pay a dispatch (the host engine's
#: short-run machinery is faster)
MIN_SPAN = 64
#: residual ops (including the blocking op) replayed on host per cut
REPLAY_OPS = 32
#: max ops per dispatch (shape-bucket cap; windows chunk above this)
W_MAX = 8192
#: consecutive low-progress dispatches before the window goes host
_STALL_CALLS = 3
_STALL_NE = 16


def _bucket(m: int) -> int:
    """Window arrays are always padded to W_MAX: the op loop runs only
    ``n`` iterations, so padding costs a few hundred KB of entry
    copies per dispatch while pinning the executor to exactly one XLA
    compile per slot-count geometry (a multi-second compile per shape
    bucket would otherwise dominate the batch wall)."""
    return W_MAX


class _Resident:
    """One KN's device-resident cache state within a batch."""

    __slots__ = ("cache", "kn_name", "state", "nslots", "kind0",
                 "demo0", "evic0", "ms0")


class JitEngine:
    """Per-cluster driver; created lazily on the first jit batch."""

    def __init__(self, cluster):
        self.cluster = cluster
        # the cluster's spans (jit.*) and counters
        self.telemetry = cluster.telemetry
        self.resident: dict[str, _Resident] = {}
        self._vmax: dict[float, object] = {}      # amr -> device table
        self._pm_token = None                     # probe_map identity
        self._pm_ptr = self._pm_len = None
        self._pm_probes = self._pm_bucket = None
        # the most state bytes resident at once
        self.resident_bytes_peak = 0
        # lazy import so merely constructing a cluster never pulls jax
        from ..kernels import batch_executor as be
        self.be = be

    @property
    def counts(self):
        """The cluster's counters.  Where each op ran: "dispatches",
        "device_ops", and "host_ops.<reason>" for every op the host
        engine ran instead (a cut reason of batch_executor.ref.CUT_NAMES,
        or delete / short_span / stall / short_window / int32_guard);
        the state bytes moved: "jit.upload_bytes", "jit.sync_bytes";
        "jit.make_space_ops", the device ops whose make-space demoted or
        evicted at least one entry."""
        return self.telemetry.counts

    @property
    def upload_s(self) -> float:
        """Seconds in ``jit.upload`` spans (read by bench/lib/harness.py
        under this name)."""
        return self.telemetry.total("jit.upload")

    # ----- per-batch context ---------------------------------------------
    def _ensure_pm(self, probe_map, nbatch, pool) -> None:
        """Densify the batch's probe prefetch map once (dict -> arrays
        indexed by global batch position)."""
        if self._pm_token is probe_map:
            return
        be = self.be
        with self.telemetry.span("jit.probe_map"):
            pm_ptr = np.full(nbatch, be.PM_INVALID, np.int64)
            pm_len = np.zeros(nbatch, np.int64)
            pm_probes = np.zeros(nbatch, np.float64)
            pm_bucket = np.full(nbatch, -1, np.int64)
            hl = pool.heap_len
            for p, (pp, probes, bk) in probe_map.items():
                if pp is None:
                    pm_ptr[p] = be.PM_ABSENT
                else:
                    pm_ptr[p] = pp
                    pm_len[p] = hl[pp]
                pm_probes[p] = probes
                pm_bucket[p] = bk
        self._pm_ptr, self._pm_len = pm_ptr, pm_len
        self._pm_probes, self._pm_bucket = pm_probes, pm_bucket
        self._pm_token = probe_map

    def end_batch(self) -> None:
        """Scatter every resident KN back and drop batch context."""
        self.sync_all()
        self._pm_token = None
        self._pm_ptr = self._pm_len = None
        self._pm_probes = self._pm_bucket = None

    # ----- residency -----------------------------------------------------
    def _upload(self, kn, cache, plan):
        """Pack the cache into device state; None if the int32 ranges
        (or a non-positive capacity) rule the device program out."""
        with self.telemetry.span("jit.upload", kn=kn.name):
            be = self.be
            nslots = cache.kind.shape[0]
            if not (0 < cache.capacity < _GUARD):
                return None
            if cache._clock >= _GUARD or nslots >= _I31:
                return None
            if len(self.cluster.pool.heap_val) >= _I31:
                return None    # covers every staged/prefetched pointer
            live = cache.kind != 0
            if live.any():
                if int(cache.count[live].max()) >= _GUARD:
                    return None
                if int(cache.ptr[live].max()) >= _I31:
                    return None
                if int(cache.length[live].max()) >= _GUARD:
                    return None
            # the guards above proved every value fits int32: narrow
            # here, explicitly, so the device state never depends on an
            # implicit int64 -> int32 conversion.  The victim trees want
            # a power-of-two leaf count; pad with absent entries (never
            # addressed: keys are < nslots)
            pad = 1
            while pad < nslots:
                pad <<= 1
            arrs = []
            for a in (cache.kind, cache.count, cache.stamp, cache.length,
                      cache.ptr):
                a32 = np.zeros(pad, np.int32)
                a32[:nslots] = a.astype(np.int32)
                arrs.append(a32)
            state = be.init_state(arrs[0], arrs[1], arrs[2], arrs[3],
                                  arrs[4], cache._cnt_hist,
                                  cache.used, cache._clock,
                                  cache._zero_shortcuts, cache._nvals,
                                  cache._nshort)
            import jax.numpy as jnp
            res = _Resident()
            res.cache = cache
            res.kn_name = kn.name
            res.kind0 = state[0]                  # host int32 shadow
            res.state = tuple(jnp.asarray(a) for a in state)
            res.nslots = nslots
            res.demo0 = 0
            res.evic0 = 0
            res.ms0 = 0
        nbytes = sum(a.nbytes for a in state)
        self.telemetry.counts["jit.upload_bytes"] += nbytes
        held = nbytes + sum(
            a.nbytes for r in self.resident.values() for a in r.state)
        self.resident_bytes_peak = max(self.resident_bytes_peak, held)
        return res

    def sync_kn(self, name: str) -> None:
        """Scatter a resident KN's device state back into its cache
        (arrays, scalars, histogram) and re-seed lazy-heap records for
        entries whose kind changed on device."""
        res = self.resident.pop(name, None)
        if res is None:
            return
        with self.telemetry.span("jit.sync", kn=name):
            be = self.be
            host = [np.asarray(a) for a in res.state]
            self.telemetry.counts["jit.sync_bytes"] += sum(
                a.nbytes for a in host)
            kind, count, stamp, length, ptr, _wrote, hist, regs = host
            cache = res.cache
            ns = res.nslots
            with sanitize.owned(res.kn_name):
                # device arrays are padded to a power of two; only the
                # first ns slots are real (pad entries are never
                # addressed)
                cache.kind[:ns] = kind[:ns].astype(np.int8)
                cache.count[:ns] = count[:ns]
                cache.stamp[:ns] = stamp[:ns]
                cache.length[:ns] = length[:ns]
                cache.ptr[:ns] = ptr[:ns]
            cache._cnt_hist[:] = hist.tolist()
            cache.used = int(regs[be.R_USED])
            cache._clock = int(regs[be.R_CLOCK])
            cache._zero_shortcuts = int(regs[be.R_ZSHORT])
            cache._nvals = int(regs[be.R_NVALS])
            cache._nshort = int(regs[be.R_NSHORT])
            # entries whose kind survived keep their lazy-heap records
            # (stale stamps/counts self-heal on pop); changed kinds need
            # one fresh record to stay visible to victim selection
            lru, lfu = cache._lru, cache._lfu
            for k in np.nonzero(kind != res.kind0)[0].tolist():
                kd = int(kind[k])
                if kd == 2:
                    heapq.heappush(lru, (int(stamp[k]), k))
                elif kd == 1:
                    heapq.heappush(lfu, (int(count[k]), k))

    def sync_all(self) -> None:
        for name in list(self.resident):
            self.sync_kn(name)

    # ----- promote threshold table ---------------------------------------
    def _vmax_for(self, cache):
        amr = float(cache.avg_miss_rts)
        t = self._vmax.get(amr)
        if t is None:
            import jax.numpy as jnp
            if len(self._vmax) > 128:
                self._vmax.clear()
            t = jnp.asarray(self.be.build_promote_table(
                amr, float(cache.avg_shortcut_hit_rts)))
            self._vmax[amr] = t
        return t

    # ----- window execution ----------------------------------------------
    def run_window(self, w, full, keys, kinds, plan, probe_map, dkeys,
                   dbuckets, out_values) -> bool:
        """Execute one KN window (global positions ``full``) through
        the device engine.  Returns False when the window is ineligible
        (caller falls back to the host engine untouched)."""
        kn, cache = w.kn, w.cache
        name = kn.name
        if full.size < MIN_SPAN and name not in self.resident:
            self.counts["host_ops.short_window"] += full.size
            return False
        c = self.cluster
        self._ensure_pm(probe_map, keys.shape[0], c.pool)
        if name not in self.resident:
            res = self._upload(kn, cache, plan)
            if res is None:
                self.counts["host_ops.int32_guard"] += full.size
                return False
            self.resident[name] = res
        skeys = keys[full]
        sops = kinds[full]
        dpos = np.nonzero(sops == 2)[0]
        di = 0
        lo = 0
        nall = full.size
        stall = 0
        while lo < nall:
            while di < dpos.size and dpos[di] < lo:
                di += 1
            seg_end = int(dpos[di]) if di < dpos.size else nall
            if stall >= _STALL_CALLS:
                self._host_replay(kn, cache, full, skeys, sops, lo,
                                  nall, plan, probe_map, dkeys,
                                  dbuckets, out_values, "stall")
                return True
            if seg_end == lo:
                # the op is a delete: segcache pops and invalidation
                # order stay host-side
                self._host_replay(kn, cache, full, skeys, sops, lo,
                                  lo + 1, plan, probe_map, dkeys,
                                  dbuckets, out_values, "delete")
                lo += 1
                continue
            if seg_end - lo < MIN_SPAN and name not in self.resident:
                # too short to pay a fresh upload: run through the
                # next delete on host, then resume
                host_end = min(seg_end + 1, nall)
                self._host_replay(kn, cache, full, skeys, sops, lo,
                                  host_end, plan, probe_map, dkeys,
                                  dbuckets, out_values, "short_span")
                lo = host_end
                continue
            if name not in self.resident:
                res = self._upload(kn, cache, plan)
                if res is None:
                    self._host_replay(kn, cache, full, skeys, sops, lo,
                                      nall, plan, probe_map, dkeys,
                                      dbuckets, out_values, "int32_guard")
                    return True
                self.resident[name] = res
            res = self.resident[name]
            n = min(seg_end - lo, W_MAX)
            ne, cut = self._dispatch(kn, cache, res, full, skeys, sops,
                                     lo, n, plan, dkeys, dbuckets,
                                     out_values)
            lo += ne
            if cut:
                stall = stall + 1 if ne < _STALL_NE else 0
                r_end = min(lo + REPLAY_OPS, seg_end)
                self._host_replay(kn, cache, full, skeys, sops, lo,
                                  r_end, plan, probe_map, dkeys,
                                  dbuckets, out_values,
                                  self.be.CUT_NAMES[cut])
                lo = r_end
            else:
                stall = 0
        return True

    def _host_replay(self, kn, cache, full, skeys, sops, lo, hi, plan,
                     probe_map, dkeys, dbuckets, out_values,
                     reason: str) -> None:
        """Hand [lo, hi) to the host engine's exact per-op machinery
        (scattering the device state back first)."""
        if hi <= lo:
            return
        self.counts["host_ops." + reason] += hi - lo
        self.sync_kn(kn.name)
        self.cluster._replay_span(kn, cache, True, full[lo:hi],
                                  skeys[lo:hi], sops[lo:hi], plan,
                                  probe_map, dkeys, dbuckets,
                                  out_values)

    # ----- one device dispatch + host fold --------------------------------
    def _dispatch(self, kn, cache, res, full, skeys, sops, lo, n, plan,
                  dkeys, dbuckets, out_values):
        be = self.be
        tel = self.telemetry
        name = kn.name
        with tel.span("jit.prep", kn=name):
            hi = lo + n
            spos = full[lo:hi]
            ck = skeys[lo:hi]
            co = sops[lo:hi]
            wpad = _bucket(n)
            ops32 = np.zeros(wpad, np.int32)
            keys32 = np.zeros(wpad, np.int32)
            wptr32 = np.zeros(wpad, np.int32)
            pmp = np.full(wpad, be.PM_INVALID, np.int64)
            pml = np.zeros(wpad, np.int32)
            seg0 = np.zeros(wpad, np.int32)
            ops32[:n] = co                         # deletes were clamped out
            keys32[:n] = ck
            if plan.nw:
                wr = plan.wrank[spos]
                wptr32[:n] = plan.ptrs[np.maximum(wr, 0)]
            pmp[:n] = self._pm_ptr[spos]
            pml[:n] = self._pm_len[spos]
            # a prefetch stays valid only while its key and bucket are
            # untouched by mid-batch merges (the pool's dirty sets)
            if dkeys:
                dk = np.fromiter(dkeys, np.int64, len(dkeys))
                pmp[:n][np.isin(ck, dk)] = be.PM_INVALID
            if dbuckets:
                db = np.fromiter(dbuckets, np.int64, len(dbuckets))
                pmp[:n][np.isin(self._pm_bucket[spos], db)] = be.PM_INVALID
            segd = kn.segcache
            if segd:
                sk = np.fromiter(segd.keys(), np.int64, len(segd))
                seg0[:n] = np.isin(ck, sk)
            vmax = self._vmax_for(cache)

        with tel.span("jit.dispatch", kn=name):
            out = be.fused_window(res.state, ops32, keys32, wptr32,
                                  pmp.astype(np.int32), pml, seg0, n,
                                  cache.capacity, self.cluster.value_bytes,
                                  vmax)
            ne = int(out[0])
            res.state = out[1]           # the passed state was donated
            tel.counts["dispatches"] += 1
            tel.counts["device_ops"] += ne
            events = np.asarray(out[2])[:ne]
            out_ptr = np.asarray(out[3])[:ne]
            cut = int(out[4])
            regs = np.asarray(out[1][7])

        with tel.span("jit.fold", kn=name):
            self._fold(kn, cache, res, spos[:ne], ck[:ne], events, out_ptr,
                       regs, plan, out_values)
        return ne, cut

    def _fold(self, kn, cache, res, ps, ks, ev, out_ptr, regs, plan,
              out_values) -> None:
        """Fold one executed prefix into the host bookkeeping exactly
        as the host engine would have: stats, RT sums (integer-valued
        floats, so grouping cannot change the result), the sequential
        miss-EMA refold in op order, ordered segment-cache puts, and
        collected read values."""
        be = self.be
        ne = ev.size
        if ne == 0:
            return
        st = kn.stats
        cs = cache.stats
        cnt = np.bincount(ev, minlength=6)
        nwr = int(cnt[be.EV_WRITE])
        npr = int(cnt[be.EV_PROMOTE])
        nsh = int(cnt[be.EV_SHORTCUT_HIT])
        st.ops += ne
        st.reads += ne - nwr
        st.writes += nwr
        cs.value_hits += int(cnt[be.EV_VALUE_HIT])
        cs.shortcut_hits += nsh + npr
        cs.promotions += npr
        cs.misses += int(cnt[be.EV_MISS_FILL]) + int(cnt[be.EV_MISS_ABSENT])
        cs.demotions += int(regs[be.R_DEMOTIONS]) - res.demo0
        cs.evictions += int(regs[be.R_EVICTIONS]) - res.evic0
        res.demo0 = int(regs[be.R_DEMOTIONS])
        res.evic0 = int(regs[be.R_EVICTIONS])
        self.counts["jit.make_space_ops"] += int(regs[be.R_MS_OPS]) - res.ms0
        res.ms0 = int(regs[be.R_MS_OPS])
        rts = float(nsh + npr)                 # shortcut chases: 1 RT
        mf = np.nonzero(ev == be.EV_MISS_FILL)[0]
        if mf.size:
            pr = self._pm_probes[ps[mf]]
            rts += float(pr.sum()) + mf.size   # traversal + value fetch
            ema = cache._ema
            a = cache.avg_miss_rts
            for r in pr.tolist():              # EMA refold in op order
                a += ema * (r + 1.0 - a)
            cache.avg_miss_rts = a
            if int(regs[be.R_EMA_DIRTY]):
                # the threshold table is rebuilt from the new EMA, so
                # the device's staleness latch can drop
                regs = regs.copy()
                regs[be.R_EMA_DIRTY] = 0
                import jax.numpy as jnp
                res.state = res.state[:7] + (jnp.asarray(regs),)
        ma = np.nonzero(ev == be.EV_MISS_ABSENT)[0]
        if ma.size:
            rts += float(self._pm_probes[ps[ma]].sum())
        wsel = np.nonzero(ev == be.EV_WRITE)[0]
        if wsel.size:
            wr = plan.wrank[ps[wsel]]
            rts += float(plan.rts[wr].sum())
            segd = kn.segcache
            vb = self.cluster.value_bytes
            kw = ks[wsel].tolist()
            segd.update(zip(kw, ((p, vb) for p in
                                 plan.ptrs[wr].tolist())))
            # C-level move_to_end sweep keeps last-put order; trimming
            # afterwards equals per-put trimming (LRU invariant)
            any(map(segd.move_to_end, kw))
            cap = kn.segcache_cap
            while len(segd) > cap:
                segd.popitem(last=False)
        st.rts += rts
        if out_values is not None:
            hv = self.cluster.pool.heap_val
            rsel = np.nonzero(ev <= be.EV_MISS_FILL)[0]
            for p_, q in zip(ps[rsel].tolist(),
                             out_ptr[rsel].tolist()):
                out_values[p_] = hv[q]
