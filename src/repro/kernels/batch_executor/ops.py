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

Five lowering choices keep each op to a few small launches; getting
one wrong can turn them into whole-array memory traffic or multiply
them.  ``tests/test_chip_compile.py`` checks the result on the program
compiled for a TPU v5e at the served slot count: bounded launches per
op and per make-space iteration, no register-array update and one
block-array write per tree update in either loop, no conditional and
no whole-array copy or scatter in either loop, no large gather at
entry.

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
  lane, one reduce over the leaf's block, one in-place column write
  into the block array.  A binary tree costs a launch per level and
  lane as per-level arrays, and a pass over the whole lane as one
  flat array written by a scatter (a TPU scatter reads and writes its
  whole operand).  Leaf lanes stay separate 1-D arrays: a ``(n,
  lanes)`` layout would be tiled ``(8, 128)`` on the TPU, its minor
  dimension padded 32x.  A tree's block minima are one ``[lanes,
  n/f]`` array, lanes on the major axis, so an update writes one
  column, not one block entry per lane.

* Scalars where an array update would launch.  The registers ride
  both loops as scalar carries, unpacked at entry and packed once at
  exit: an update of a register array is a launch of its own, scalar
  arithmetic is not.  An op's histogram moves land as one elementwise
  add of one-hot deltas after make-space (which adds to the histogram
  but never reads it), and ``events``/``out_ptr`` are the two rows of
  one array, written one column per op.

* No dead tree writes.  Taking the op's key out of both victim pools
  matters only to make-space, so it is the first iteration of the
  make-space loop, not a step of every op: an op that needs no victim
  (nearly every hit, and every write or fill that fits) writes each of
  its tree leaves once, after the loop.

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
                  EV_VALUE_HIT, EV_WRITE, NUM_REGS, PM_ABSENT,
                  PM_INVALID, R_CLOCK, R_DEMOTIONS, R_EMA_DIRTY,
                  R_EVICTIONS, R_MS_OPS, R_NSHORT, R_NVALS, R_USED,
                  R_ZSHORT, SHORTCUT_BYTES, VALUE_OVERHEAD_BYTES)

_BIG = jnp.int32(2 ** 31 - 1)
_SB = SHORTCUT_BYTES
_VOB = VALUE_OVERHEAD_BYTES
_HM = CNT_HIST_MAX


def _i32(b):
    return b.astype(jnp.int32)


# ----- two-level min-trees over (value, key, *payloads) ------------------
# A tree is (leaf lanes, block array).  Leaf lanes are (value,
# *payloads), one 1-D array of n entries each; leaf i holds key i, never
# materialized (a carried constant arange would cost XLA a full-array
# copy per loop iteration).  The leaves split into contiguous blocks of
# f = 2^ceil(log2(n)/2); the block array is [L, n/f], row j the j-th of
# (value, key, *payloads) of each block's (value, key) minimum, so an
# update writes one [L, 1] column.  The root is the minimum of the
# blocks, taken when asked for.
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
    return leaves, jnp.stack(blocks)


def _tree_set(tree, k, val, pvals=()):
    """Set leaf k to val (+ payloads) and re-min its block: one
    in-place write per leaf lane, one reduce over the block's f
    contiguous leaves, one in-place column write into the block array
    -- whatever the slot count.  (A scatter of several entries is no
    substitute: on the TPU it passes over its whole operand.)"""
    leaves, blocks = tree
    f = leaves[0].shape[0] // blocks.shape[1]
    leaves = tuple(l.at[k].set(x) for l, x in zip(leaves, (val,)
                                                  + tuple(pvals)))
    b = k // f
    blk = [lax.dynamic_slice(l, (b * f,), (f,)) for l in leaves]
    keys = b * f + jnp.arange(f, dtype=jnp.int32)
    best = _lexmin([blk[0], keys] + blk[1:], 0)
    return leaves, _write_column(blocks, best, b)


def _write_column(a, vals, j):
    """``a`` with column j set to the scalars ``vals``: one in-place
    write.  The column is built elementwise (a stack of scalars would
    lower to a concatenate and a layout copy)."""
    row = lax.broadcasted_iota(jnp.int32, (len(vals), 1), 0)
    col = jnp.broadcast_to(vals[-1], (len(vals), 1))
    for r, v in enumerate(vals[:-1]):
        col = jnp.where(row == r, v, col)
    return lax.dynamic_update_slice(a, col, (jnp.int32(0), j))


def _tree_min(tree):
    """(min value, its key, *payloads) -- the lazy-heap pop order."""
    return _lexmin(tuple(tree[1]), 0)


def _lru_set(tr, k, val, ln, cnt):
    return (_tree_set(tr[0], k, val, (ln, cnt)), tr[1])


def _lfu_set(tr, k, val):
    return (tr[0], _tree_set(tr[1], k, val))


def _hist_add(hist, deltas):
    """``hist`` plus each (count, delta) of ``deltas`` at bucket
    min(count, CNT_HIST_MAX): one elementwise add of one-hot deltas,
    not one write per pair."""
    b = jnp.arange(hist.shape[0], dtype=jnp.int32)
    return hist + sum(_i32(b == jnp.minimum(c, _HM)) * d
                      for c, d in deltas)


# ----- make-space (mirrors ArrayDAC._make_space 1:1) ---------------------
def _make_space(hist, regs, tr, need, cap, k, k_leaves):
    """Demote LRU values (reinsert as a shortcut when room remains),
    then evict LFU shortcuts, until ``need`` bytes fit.  ``need`` = 0
    degenerates to a no-op (used <= cap is an invariant), which is how
    ops that free their own room skip this entirely.

    One predicated loop: demote strictly while values remain, then
    evict -- the same victim sequence as the reference's two loops.
    The carry holds only what the loop writes; victim metadata comes
    from the tree roots (see the tree comment).

    The first iteration selects no victim: it sets the op's key k to
    ``k_leaves`` = (LRU value, length, count, LFU value), which takes k
    out of both victim pools (or keeps a hit's new stamp), so k can
    never be selected against itself.  It leaves the registers as they
    are, so a victim iteration always follows, and counts the op in
    R_MS_OPS.  An op whose make-space finds room at once runs no
    iteration and writes k's leaves once, after the loop."""

    def cond(c):
        r = c[1]
        return (r[R_USED] + need > cap) \
            & ((r[R_NVALS] > 0) | (r[R_NSHORT] > 0))

    def body(c):
        hist, r, tr = c
        r = list(r)
        first = r[R_MS_OPS] == ms0
        dem = ~first & (r[R_NVALS] > 0)
        evi = ~first & (r[R_NVALS] == 0)
        _, v_d, ln, cv_d = _tree_min(tr[0])
        cv_e, v_e = _tree_min(tr[1])
        v = jnp.where(dem, v_d, v_e)
        cv = jnp.where(dem, cv_d, cv_e)
        used_d = r[R_USED] - (ln + _VOB)
        reins = dem & (used_d + _SB + need <= cap)
        d_short = _i32(reins) - _i32(evi)
        hist = hist.at[jnp.minimum(cv, _HM)].add(d_short)
        r[R_USED] = jnp.where(dem, used_d + _SB * _i32(reins),
                              r[R_USED] - _SB * _i32(evi))
        r[R_NVALS] -= _i32(dem)
        r[R_DEMOTIONS] += _i32(dem)
        r[R_NSHORT] += d_short
        r[R_ZSHORT] += d_short * _i32(cv == 0)
        r[R_EVICTIONS] += _i32(evi)
        r[R_MS_OPS] += _i32(first)
        # a demoted value leaves the LRU pool; an evicted (or
        # non-reinserted) shortcut leaves the LFU pool -- no reads
        # (an evicted shortcut's LRU leaf is already +inf)
        lru_k, ln_k, cnt_k, lfu_k = k_leaves
        kv = jnp.where(first, k, v)
        tr = _lru_set(tr, kv, jnp.where(first, lru_k, _BIG),
                      jnp.where(first, ln_k, ln),
                      jnp.where(first, cnt_k, cv))
        tr = _lfu_set(tr, kv, jnp.where(
            first, lfu_k, jnp.where(reins, cv, _BIG)))
        return hist, r, tr

    ms0 = regs[R_MS_OPS]
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
    # vectorized tree build: O(n) at entry, O(sqrt n) per update after
    tr0 = (_tree_build(jnp.where(kind0 == 2, state[2], _BIG),
                       payloads=(state[3], state[1])),
           _tree_build(jnp.where(kind0 == 1, state[1], _BIG)))
    # the registers ride both loops as scalars (a register update is
    # then scalar arithmetic, not a launch) and are packed once at exit
    regs0 = [state[7][j] for j in range(NUM_REGS)]

    def cond(carry):
        i, cut = carry[0], carry[1]
        return (i < n) & (cut == CUT_NONE)

    def body(carry):
        i, _, st, r, tr, rec = carry
        count, stamp, length, ptr, wrote, hist = st
        r = list(r)
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
        cut_s, promote = _promote_precheck(hist, r, c1, ln_old, cap,
                                           vmax)
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

        # ---- phase B1: removal + hit bookkeeping (registers; the
        # histogram, entry-field and tree writes land in B3) ----------
        clock0 = r[R_CLOCK]
        r[R_USED] -= (_i32(a_w) * (_i32(was_v) * (ln_old + _VOB)
                                   + _i32(was_s) * _SB)
                      + _i32(pro) * _SB)
        r[R_NVALS] -= _i32(a_w & was_v)
        r[R_NSHORT] -= _i32(a_w & was_s) + _i32(pro)
        r[R_ZSHORT] -= (_i32(a_w & was_s & (c_old == 0))
                        + _i32(a_s & (c1 == 1)))
        r[R_CLOCK] += _i32(a_v)
        r[R_EMA_DIRTY] = jnp.maximum(r[R_EMA_DIRTY], _i32(a_m))
        hist_b1 = [(c_old, -_i32(a_w & was_s)), (c1 - 1, -_i32(a_s)),
                   (c1, _i32(a_s & ~promote))]
        wrote = wrote.at[k].set(wrote[k] | _i32(a_w))
        # k's leaves once it has left both victim pools; make-space
        # writes them before its first victim, B3 writes k's final ones
        leaf_lru1 = jnp.where(a_v, clock0,
                              jnp.where(a_w, _BIG,
                                        jnp.where(was_v, stamp_old,
                                                  _BIG)))
        cnt_pay = jnp.where(a_v, c1, c_old)
        leaf_lfu1 = jnp.where(a_s & ~promote, c1,
                              jnp.where(a_w | pro, _BIG,
                                        jnp.where(was_s, c_old,
                                                  _BIG)))

        # ---- phase B2: one unified make-space ------------------------
        used1 = r[R_USED]
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
        hist, r, tr = _make_space(hist, r, tr, need, cap, k,
                                  (leaf_lru1, ln_old, cnt_pay,
                                   leaf_lfu1))
        r = list(r)

        # ---- phase B3: insert / final entry-field writes -------------
        used2 = r[R_USED]
        clock2 = r[R_CLOCK]
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
        r[R_USED] += _i32(do_v) * (ln_ins + _VOB) + _i32(do_s) * _SB
        r[R_NVALS] += _i32(do_v)
        r[R_NSHORT] += _i32(do_s)
        r[R_ZSHORT] += _i32(do_s & (cnt_ins == 0))
        r[R_CLOCK] += _i32(do_v)
        # B1's histogram moves wait for this one write: make-space only
        # adds to the histogram, it never reads it
        hist = _hist_add(hist, hist_b1 + [(cnt_ins, _i32(do_s))])
        tr = _lru_set(tr, k, jnp.where(do_v, clock2, leaf_lru1),
                      jnp.where(do_v, ln_ins, ln_old),
                      jnp.where(do_v, cnt_ins, cnt_pay))
        tr = _lfu_set(tr, k, jnp.where(do_s, cnt_ins, leaf_lfu1))
        st = (count, stamp, length, ptr, wrote, hist)

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
        # write above and cost XLA a whole-array defensive copy
        outp = jnp.where(is_write, wptr[i],
                         jnp.where(is_miss,
                                   jnp.where(absent, jnp.int32(-1),
                                             pp),
                                   ptr[k]))
        # events and out_ptr are the rows of one array: one write an op
        rec = _write_column(rec, (ev, outp), i)
        return i + _i32(act), cut, st, r, tr, rec

    w = ops.shape[0]
    rec = jnp.stack([jnp.zeros(w, jnp.int32),
                     jnp.full(w, -1, jnp.int32)])
    i, cut, st, r, tr, rec = lax.while_loop(
        cond, body, (jnp.int32(0), jnp.int32(CUT_NONE), state[1:7],
                     regs0, tr0, rec))
    # the kind array was derived from the trees throughout; rebuild it
    # once, vectorized, for the returned state
    kind = jnp.where(tr[0][0][0] != _BIG, jnp.int32(2),
                     jnp.where(tr[1][0][0] != _BIG, jnp.int32(1),
                               jnp.int32(0)))
    return i, (kind,) + st + (jnp.stack(r),), rec[0], rec[1], cut


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
