"""Jitted fused batch executor: one XLA program per KN window.

``fused_window`` is the compiled twin of ``ref.fused_window_ref`` --
the same sequential per-op DAC state machine (value/shortcut hits,
Eq. 1 promotions with the full make-space loop, prefetch-resolved
misses, staged write fills) lowered onto device-resident state with
donated buffers, so a window executes as a single dispatch with no
per-chunk host round-trips.  The host driver (``repro.core.
jit_engine``) keeps the arrays resident across the windows of a batch
and only scatters back at a truncation signal, a host-side touch, or
batch end.

Exactness: every arithmetic decision is integer (the float Eq. 1
comparison is discretized into the host-built promote threshold table,
see ``ref.build_promote_table``), so the compiled path cannot drift
from the reference by a rounding flip.

Four lowering choices keep each op to a few small launches; getting
one wrong can turn them into whole-array memory traffic or multiply
them.  ``tests/test_chip_compile.py`` checks the result on the program
compiled for a TPU v5e at the served slot count: bounded fusions per
op and per make-space iteration, no conditional and no whole-array
copy or scatter in either loop, no large gather at entry.

* Victim selection.  The reference's lazy LRU/LFU heaps become two
  min-trees over (value, key) -- LRU over value-entry stamps, LFU over
  shortcut counts, absent entries at +inf -- built vectorized at
  dispatch entry (O(n)) and kept with O(sqrt n) leaf updates as ops
  mutate entries.  The root is exactly what the lazy heaps pop: argmin
  (stamp, key) / argmin (count, key) over live entries.  A flat argmin
  would be O(n) *per eviction*.  Each tree has two levels: the leaves,
  and the minima of contiguous blocks of ~sqrt(n) leaves, so the build
  is one reduce over a reshape (no strided pairing, which lowers to
  gathers at ~36 M elements/s on the chip), and a leaf update is the
  same few launches at any slot count: an in-place write per leaf
  lane, one reduce over the leaf's block, an in-place write per block
  lane.  A binary tree costs a launch per level and lane as
  per-level arrays, and a pass over the whole lane as one flat array
  written by a scatter (a TPU scatter reads and writes its whole
  operand).  Lanes stay separate 1-D arrays: a ``(n, lanes)`` layout
  would be tiled ``(8, 128)`` on the TPU, its minor dimension padded
  32x.

* Predication, not branching.  The per-op state machine is a
  straight-line body of masked scalar scatters.  ``lax.cond`` /
  ``lax.switch`` branches returning the full state force XLA to
  materialize a copy of every carried array per op.

* The make-space loop reads nothing it does not write.  XLA copies
  any buffer a nested while reads but never writes, once per
  enclosing-loop iteration -- so the victim scan must not gather from
  the entry-field arrays.  The LRU tree carries each value's length
  and count as payload lanes propagated alongside the winning
  (stamp, key); the LFU min *is* the count; and the victim's leaf
  rewrites need no reads (a demoted value's LRU leaf and an evicted
  shortcut's LFU leaf both go to +inf).  Demotes and evicts share one
  while loop -- demote strictly while values remain, then evict --
  which matches the reference's two sequential loops and halves the
  nested-boundary crossings.

* Hits read the post-update ``ptr`` (see the body): re-reading the
  pre-update array would anti-depend on its in-place write, which can
  cost a whole-array copy per op.

This is a pure ``jax.jit``/``lax`` program (no Pallas), so it needs no
interpret-mode resolution.  Slot count must be a power of two (the
driver pads; padding slots are absent entries and never referenced).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .ref import (CNT_HIST_MAX, CUT_EMA, CUT_NONE, CUT_PREFETCH,
                  CUT_SEGCACHE, CUT_SPILL, CUT_TABLE, EV_MISS_ABSENT,
                  EV_MISS_FILL, EV_PROMOTE, EV_SHORTCUT_HIT,
                  EV_VALUE_HIT, EV_WRITE, PM_ABSENT, PM_INVALID,
                  R_CLOCK, R_DEMOTIONS, R_EMA_DIRTY, R_EVICTIONS,
                  R_NSHORT, R_NVALS, R_USED, R_ZSHORT, SHORTCUT_BYTES,
                  VALUE_OVERHEAD_BYTES)

_BIG = jnp.int32(2 ** 31 - 1)
_SB = SHORTCUT_BYTES
_VOB = VALUE_OVERHEAD_BYTES
_HM = CNT_HIST_MAX


def _i32(b):
    return b.astype(jnp.int32)


# ----- two-level min-trees over (value, key, *payloads) ------------------
# A tree is (leaf lanes, block lanes).  Leaf lanes are (value,
# *payloads), one 1-D array of n entries each; leaf i holds key i, never
# materialized (a carried constant arange would cost XLA a full-array
# copy per loop iteration).  The leaves split into contiguous blocks of
# f = 2^ceil(log2(n)/2); block lanes are (value, key, *payloads) of each
# block's (value, key) minimum, n/f entries each.  The root is the
# minimum of the blocks, taken when asked for.
def _lexmin(lanes, axis):
    """(value, key, *payloads) of the (value, key) minimum along
    ``axis``: one variadic reduce."""
    def pick(a, b):
        tb = (b[0] < a[0]) | ((b[0] == a[0]) & (b[1] < a[1]))
        return tuple(jnp.where(tb, y, x) for x, y in zip(a, b))

    init = (_BIG, _BIG) + (jnp.int32(0),) * (len(lanes) - 2)
    return lax.reduce(tuple(lanes), init, pick, (axis,))


def _tree_build(vals, payloads=()):
    """(value, key) min-tree over leaves ``vals``; ``payloads`` are
    extra leaf lanes carried with each block's winner."""
    n = vals.shape[0]
    f = 1 << (n.bit_length() // 2)   # n = 2^d -> f = 2^ceil(d/2)
    leaves = (vals,) + tuple(payloads)
    keys = jnp.arange(n, dtype=jnp.int32)
    blocks = _lexmin([l.reshape(n // f, f) for l in (vals, keys)
                      + tuple(payloads)], 1)
    return leaves, blocks


def _tree_set(tree, k, val, pvals=()):
    """Set leaf k to val (+ payloads) and re-min its block: one
    in-place write per leaf lane, one reduce over the block's f
    contiguous leaves, one in-place write per block lane -- whatever
    the slot count.  (A scatter of several entries is no substitute:
    on the TPU it passes over its whole operand.)"""
    leaves, blocks = tree
    f = leaves[0].shape[0] // blocks[0].shape[0]
    leaves = tuple(l.at[k].set(x) for l, x in zip(leaves, (val,)
                                                  + tuple(pvals)))
    b = k // f
    blk = [lax.dynamic_slice(l, (b * f,), (f,)) for l in leaves]
    keys = b * f + jnp.arange(f, dtype=jnp.int32)
    best = _lexmin([blk[0], keys] + blk[1:], 0)
    return leaves, tuple(l.at[b].set(x) for l, x in zip(blocks, best))


def _tree_min(tree):
    """(min value, its key, *payloads) -- the lazy-heap pop order."""
    return _lexmin(tree[1], 0)


def _lru_set(tr, k, val, ln, cnt):
    return (_tree_set(tr[0], k, val, (ln, cnt)), tr[1])


def _lfu_set(tr, k, val):
    return (tr[0], _tree_set(tr[1], k, val))


# ----- make-space (mirrors ArrayDAC._make_space 1:1) ---------------------
def _make_space(hist, regs, tr, need, cap):
    """Demote LRU values (reinsert as a shortcut when room remains),
    then evict LFU shortcuts, until ``need`` bytes fit.  ``need`` = 0
    degenerates to a no-op (used <= cap is an invariant), which is how
    ops that free their own room skip this entirely.

    One predicated loop: demote strictly while values remain, then
    evict -- the same victim sequence as the reference's two loops.
    The carry holds only what the loop writes; victim metadata comes
    from the tree roots (see the tree comment)."""

    def cond(c):
        r = c[1]
        return (r[R_USED] + need > cap) \
            & ((r[R_NVALS] > 0) | (r[R_NSHORT] > 0))

    def body(c):
        hist, r, tr = c
        dem = r[R_NVALS] > 0
        _, v_d, ln, cv_d = _tree_min(tr[0])
        cv_e, v_e = _tree_min(tr[1])
        v = jnp.where(dem, v_d, v_e)
        cv = jnp.where(dem, cv_d, cv_e)
        used_d = r[R_USED] - (ln + _VOB)
        reins = dem & (used_d + _SB + need <= cap)
        hist = hist.at[jnp.minimum(cv, _HM)].add(
            _i32(reins) - _i32(~dem))
        r = r.at[R_USED].set(
            jnp.where(dem, used_d + _SB * _i32(reins),
                      r[R_USED] - _SB))
        r = r.at[R_NVALS].add(-_i32(dem))
        r = r.at[R_DEMOTIONS].add(_i32(dem))
        r = r.at[R_NSHORT].add(jnp.where(dem, _i32(reins),
                                         jnp.int32(-1)))
        r = r.at[R_ZSHORT].add(
            (_i32(reins) - _i32(~dem)) * _i32(cv == 0))
        r = r.at[R_EVICTIONS].add(_i32(~dem))
        # a demoted value leaves the LRU pool; an evicted (or
        # non-reinserted) shortcut leaves the LFU pool -- no reads
        # (an evicted shortcut's LRU leaf is already +inf)
        tr = _lru_set(tr, v, _BIG, ln, cv)
        tr = _lfu_set(tr, v, jnp.where(reins, cv, _BIG))
        return hist, r, tr

    return lax.while_loop(cond, body, (hist, regs, tr))


def _promote_precheck(hist, r, c, ln, cap, vmax):
    """Eq. 1 as ``ref._promote_decision_precheck``: evaluated against
    the pre-op state with the hit bookkeeping shifted in; returns
    (cut_reason, promote) as int32/bool scalars."""
    need = ln + _VOB - _SB
    free = cap - r[R_USED]
    n_evict = (need - free + _SB - 1) // _SB
    zshort = r[R_ZSHORT] - _i32(c == 1)
    # victim sum over the shifted histogram: one candidate entry
    # removed at bucket c-1 when that bucket is in scanned range
    b = jnp.arange(_HM, dtype=jnp.int32)
    h = jnp.maximum(hist[:_HM] - _i32(b == c - 1), 0)
    cum = jnp.cumsum(h)
    take = jnp.clip(n_evict - (cum - h), 0, h)
    spill = jnp.sum(take) < n_evict
    vsum = jnp.sum(take * b)
    tn = vmax.shape[0]
    table_pass = vsum <= vmax[jnp.minimum(c, tn - 1)]
    # decision ladder, first matching rung wins (mirrors the reference)
    rungs = [free >= need,
             zshort >= n_evict,
             r[R_NSHORT] - 1 < n_evict,
             r[R_EMA_DIRTY] > 0,
             spill,
             c >= tn]
    cut = jnp.select(
        rungs,
        [CUT_NONE, CUT_NONE, CUT_NONE, CUT_EMA, CUT_SPILL,
         jnp.where(table_pass, CUT_NONE, CUT_TABLE)],
        CUT_NONE).astype(jnp.int32)
    promote = jnp.select(
        rungs,
        [True, True, False, False, False, table_pass],
        table_pass)
    return cut, promote


def _fused_window_impl(state, ops, keys, wptr, pm_ptr, pm_len, seg0,
                       n, cap, write_bytes, vmax):
    kind0 = state[0]
    nslots = kind0.shape[0]
    if nslots < 2 or nslots & (nslots - 1):
        raise ValueError("slot count must be a power of two >= 2")
    # vectorized tree build: O(n) at entry, O(log n) per update after
    tr0 = (_tree_build(jnp.where(kind0 == 2, state[2], _BIG),
                       payloads=(state[3], state[1])),
           _tree_build(jnp.where(kind0 == 1, state[1], _BIG)))

    def cond(carry):
        i, cut = carry[0], carry[1]
        return (i < n) & (cut == CUT_NONE)

    def body(carry):
        i, _, st, tr, events, out_ptr = carry
        count, stamp, length, ptr, wrote, hist, regs = st
        k = keys[i]
        # entry kind is derived, not carried: a key is a value entry
        # iff its LRU leaf is live, a shortcut iff its LFU leaf is --
        # keeping the dense kind array out of the loop carry spares
        # XLA a defensive whole-array copy per op (it is both read
        # here and rewritten inside make-space)
        kd = jnp.where(tr[0][0][0][k] != _BIG, jnp.int32(2),
                       jnp.where(tr[1][0][0][k] != _BIG, jnp.int32(1),
                                 jnp.int32(0)))
        c_old = count[k]
        ln_old = length[k]
        p_old = ptr[k]
        stamp_old = stamp[k]
        was_v = kd == 2
        was_s = kd == 1

        # ---- phase A: classify + cut decision (pure reads) ----------
        is_write = ops[i] == 1
        is_vhit = (~is_write) & was_v
        is_shit = (~is_write) & was_s
        is_miss = (~is_write) & (kd == 0)
        c1 = c_old + 1
        cut_s, promote = _promote_precheck(hist, regs, c1, ln_old,
                                           cap, vmax)
        pp = pm_ptr[i]
        seg = (seg0[i] > 0) | (wrote[k] > 0)
        cut_m = jnp.select([seg, pp == PM_INVALID],
                           [CUT_SEGCACHE, CUT_PREFETCH],
                           CUT_NONE).astype(jnp.int32)
        absent = pp == PM_ABSENT
        cut = jnp.where(is_shit, cut_s,
                        jnp.where(is_miss, cut_m,
                                  jnp.int32(CUT_NONE)))
        act = cut == CUT_NONE
        a_w = act & is_write
        a_v = act & is_vhit
        a_s = act & is_shit
        a_m = act & is_miss & (~absent)          # prefetch-backed fill
        pro = a_s & promote

        # ---- phase B1: removal + hit bookkeeping (regs/hist/trees;
        # the entry-field writes combine into one scatter in B3) ------
        clock0 = regs[R_CLOCK]
        regs = regs.at[R_USED].add(
            -_i32(a_w) * (_i32(was_v) * (ln_old + _VOB)
                          + _i32(was_s) * _SB)
            - _i32(pro) * _SB)
        regs = regs.at[R_NVALS].add(-_i32(a_w & was_v))
        regs = regs.at[R_NSHORT].add(-_i32(a_w & was_s) - _i32(pro))
        regs = regs.at[R_ZSHORT].add(
            -_i32(a_w & was_s & (c_old == 0)) - _i32(a_s & (c1 == 1)))
        regs = regs.at[R_CLOCK].add(_i32(a_v))
        regs = regs.at[R_EMA_DIRTY].max(_i32(a_m))
        hist = hist.at[jnp.minimum(c_old, _HM)].add(-_i32(a_w & was_s))
        hist = hist.at[jnp.minimum(c1 - 1, _HM)].add(-_i32(a_s))
        hist = hist.at[jnp.minimum(c1, _HM)].add(_i32(a_s & ~promote))
        wrote = wrote.at[k].set(wrote[k] | _i32(a_w))
        # k leaves both victim pools before make-space, so it can
        # never be selected against itself
        leaf_lru1 = jnp.where(a_v, clock0,
                              jnp.where(a_w, _BIG,
                                        jnp.where(was_v, stamp_old,
                                                  _BIG)))
        cnt_pay = jnp.where(a_v, c1, c_old)
        tr = _lru_set(tr, k, leaf_lru1, ln_old, cnt_pay)
        leaf_lfu1 = jnp.where(a_s & ~promote, c1,
                              jnp.where(a_w | pro, _BIG,
                                        jnp.where(was_s, c_old,
                                                  _BIG)))
        tr = _lfu_set(tr, k, leaf_lfu1)

        # ---- phase B2: one unified make-space ------------------------
        used1 = regs[R_USED]
        w_fits_v = used1 + write_bytes + _VOB <= cap
        ln_m = pm_len[i]
        m_fits_v = used1 + ln_m + _VOB <= cap
        # promote pays the full value need; a write or fill that fits
        # for free is prechecked (no make-space, like the reference);
        # their shortcut fallbacks need one slot's worth
        need = jnp.where(pro, ln_old + _VOB,
                         jnp.where((a_w & ~w_fits_v)
                                   | (a_m & ~m_fits_v),
                                   jnp.int32(_SB), jnp.int32(0)))
        hist, regs, tr = _make_space(hist, regs, tr, need, cap)

        # ---- phase B3: insert / final entry-field scatter ------------
        used2 = regs[R_USED]
        clock2 = regs[R_CLOCK]
        ins_any = a_w | pro | a_m
        p_ins = jnp.where(a_w, wptr[i], jnp.where(pro, p_old, pp))
        ln_ins = jnp.where(a_w, write_bytes,
                           jnp.where(pro, ln_old, ln_m))
        cpri = jnp.where(kd == 0, jnp.int32(0), c_old)
        cnt_ins = jnp.where(a_w, cpri,
                            jnp.where(pro, c1, jnp.int32(1)))
        fits_v = used2 + ln_ins + _VOB <= cap
        do_v = (a_w & w_fits_v) | (pro & fits_v) | (a_m & m_fits_v)
        do_s = ins_any & ~do_v & (used2 + _SB <= cap)
        doi = do_v | do_s
        count_f = jnp.where(doi, cnt_ins,
                            jnp.where(a_v | a_s, c1, c_old))
        stamp_f = jnp.where(do_v, clock2,
                            jnp.where(a_v, clock0, stamp_old))
        count = count.at[k].set(count_f)
        stamp = stamp.at[k].set(stamp_f)
        ptr = ptr.at[k].set(jnp.where(doi, p_ins, p_old))
        length = length.at[k].set(jnp.where(doi, ln_ins, ln_old))
        regs = regs.at[R_USED].add(
            _i32(do_v) * (ln_ins + _VOB) + _i32(do_s) * _SB)
        regs = regs.at[R_NVALS].add(_i32(do_v))
        regs = regs.at[R_NSHORT].add(_i32(do_s))
        regs = regs.at[R_ZSHORT].add(_i32(do_s & (cnt_ins == 0)))
        regs = regs.at[R_CLOCK].add(_i32(do_v))
        hist = hist.at[jnp.minimum(cnt_ins, _HM)].add(_i32(do_s))
        tr = _lru_set(tr, k, jnp.where(do_v, clock2, leaf_lru1),
                      jnp.where(do_v, ln_ins, ln_old),
                      jnp.where(do_v, cnt_ins, cnt_pay))
        tr = _lfu_set(tr, k, jnp.where(do_s, cnt_ins, leaf_lfu1))
        st = (count, stamp, length, ptr, wrote, hist, regs)

        # ---- phase B4: record + advance ------------------------------
        ev = jnp.where(
            is_write, jnp.int32(EV_WRITE),
            jnp.where(is_vhit, jnp.int32(EV_VALUE_HIT),
                      jnp.where(is_shit,
                                jnp.where(promote,
                                          jnp.int32(EV_PROMOTE),
                                          jnp.int32(EV_SHORTCUT_HIT)),
                                jnp.where(absent,
                                          jnp.int32(EV_MISS_ABSENT),
                                          jnp.int32(EV_MISS_FILL)))))
        # hits read back the just-updated ptr array (same value -- a
        # hit never moves ptr) rather than the pre-op gather: reading
        # the old array here would anti-depend on the in-place ptr
        # scatter above and cost XLA a whole-array defensive copy
        outp = jnp.where(is_write, wptr[i],
                         jnp.where(is_miss,
                                   jnp.where(absent, jnp.int32(-1),
                                             pp),
                                   ptr[k]))
        events = events.at[i].set(ev)
        out_ptr = out_ptr.at[i].set(outp)
        return i + _i32(act), cut, st, tr, events, out_ptr

    w = ops.shape[0]
    events = jnp.zeros(w, jnp.int32)
    out_ptr = jnp.full(w, -1, jnp.int32)
    i, cut, st, tr, events, out_ptr = lax.while_loop(
        cond, body, (jnp.int32(0), jnp.int32(CUT_NONE), state[1:],
                     tr0, events, out_ptr))
    # the kind array was derived from the trees throughout; rebuild it
    # once, vectorized, for the returned state
    kind = jnp.where(tr[0][0][0] != _BIG, jnp.int32(2),
                     jnp.where(tr[1][0][0] != _BIG, jnp.int32(1),
                               jnp.int32(0)))
    return i, (kind,) + st, events, out_ptr, cut


@functools.cache
def compiled_window(platform: str):
    """The jitted executor for a backend, built on first use: donation
    is an accelerator contract (the CPU backend cannot honor it and
    would warn at every compile), so the backend is asked only once a
    window actually runs -- never while the module is imported."""
    donate = () if platform == "cpu" else (0,)
    return jax.jit(_fused_window_impl, donate_argnums=donate)


def fused_window(state, ops, keys, wptr, pm_ptr, pm_len, seg0, n, cap,
                 write_bytes, vmax):
    """Run up to ``n`` window ops on device; returns ``(n_exec,
    state', events, out_ptr, cut_reason)`` exactly as
    ``fused_window_ref`` (property-tested bit-for-bit).  ``state`` is
    donated on accelerators: callers must treat the passed buffers as
    consumed."""
    return compiled_window(jax.default_backend())(
        state, ops, keys, wptr, pm_ptr, pm_len, seg0, jnp.int32(n),
        jnp.int32(cap), jnp.int32(write_bytes), vmax)
