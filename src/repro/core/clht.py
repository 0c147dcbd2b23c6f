"""P-CLHT-style hash index (paper Sec. 4, 'DPM metadata index').

The paper uses RECIPE's Persistent Cache-Line Hash Table: a chaining
hash table whose buckets are one cache line (3 key/value slots), giving
lock-free reads and log-free in-place writes -- one cache-line access
per lookup in the common case.

TPU adaptation: the table is a pytree of arrays
    keys  : (total_buckets, SLOTS) int32   (-1 == empty slot)
    ptrs  : (total_buckets, SLOTS) int32   (pointers into the value heap)
    nxt   : (total_buckets,)       int32   (chain link into overflow region)
so that
  * lookups are batched gathers (lock-free reads == pure-functional reads),
  * merges are sequential scatters applied in log order (log-free
    in-place writes == donated-buffer scatter updates),
  * the common case touches exactly one bucket row -- which is what the
    Pallas ``clht_probe`` kernel exploits (one scalar-prefetched DMA).

Two implementations with identical semantics:
  * jnp (jittable) -- used by tests, kernels and the JAX data plane;
  * numpy (NumpyCLHT) -- used by the per-op cluster simulator, where
    python-level inserts must be cheap. Equivalence is property-tested.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .telemetry import Telemetry
from .transition import (MERGE_MAX_CHAIN, _merge_bucket_batch,
                         plan_merge_window)

EMPTY = jnp.int32(-1)
SLOTS = 3          # one cache line, as in P-CLHT
MAX_CHAIN = 8      # bounded chain walk (jit-friendly)
assert MERGE_MAX_CHAIN == MAX_CHAIN  # planner mirrors the scalar walk


def _mix32(x):
    """32-bit finalizer (xxhash-style) on int32/uint32 arrays."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def bucket_of(keys, num_buckets: int):
    """Primary bucket id for each key (num_buckets must be a power of 2)."""
    return (_mix32(keys) & jnp.uint32(num_buckets - 1)).astype(jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CLHT:
    keys: jax.Array            # (total_buckets, SLOTS) int32
    ptrs: jax.Array            # (total_buckets, SLOTS) int32
    nxt: jax.Array             # (total_buckets,) int32
    overflow_head: jax.Array   # () int32: next free overflow bucket
    num_buckets: int = dataclasses.field(metadata=dict(static=True))

    @property
    def total_buckets(self) -> int:
        return self.keys.shape[0]


def clht_init(num_buckets: int, overflow_buckets: int | None = None) -> CLHT:
    assert num_buckets & (num_buckets - 1) == 0, "num_buckets must be 2^k"
    if overflow_buckets is None:
        overflow_buckets = max(num_buckets // 2, 8)
    total = num_buckets + overflow_buckets
    return CLHT(
        keys=jnp.full((total, SLOTS), EMPTY, jnp.int32),
        ptrs=jnp.full((total, SLOTS), EMPTY, jnp.int32),
        nxt=jnp.full((total,), EMPTY, jnp.int32),
        overflow_head=jnp.int32(num_buckets),
        num_buckets=num_buckets,
    )


# --------------------------------------------------------------------------
# Batched lookup (lock-free read): walk the chain up to MAX_CHAIN buckets.
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=())
def clht_lookup(table: CLHT, keys: jax.Array):
    """Returns (ptrs, found, probes): probes counts bucket rows touched --
    the paper's 'RTs for an index traversal' on a cache miss."""
    b0 = bucket_of(keys, table.num_buckets)

    def body(state, _):
        cur, ptr, found, probes, active = state
        rows_k = table.keys[cur]                       # (B, SLOTS)
        rows_p = table.ptrs[cur]
        hit = (rows_k == keys[:, None]) & active[:, None]
        hit_any = hit.any(axis=1)
        slot_ptr = jnp.where(hit, rows_p, 0).sum(axis=1)
        ptr = jnp.where(hit_any & ~found, slot_ptr, ptr)
        probes = probes + active.astype(jnp.int32)
        found = found | hit_any
        nxt = table.nxt[cur]
        active = active & ~hit_any & (nxt != EMPTY)
        cur = jnp.where(active, nxt, cur)
        return (cur, ptr, found, probes, active), None

    B = keys.shape[0]
    init = (b0, jnp.full((B,), EMPTY, jnp.int32), jnp.zeros(B, bool),
            jnp.zeros(B, jnp.int32), jnp.ones(B, bool))
    (_, ptr, found, probes, _), _ = jax.lax.scan(body, init, None,
                                                 length=MAX_CHAIN)
    return ptr, found, probes


# --------------------------------------------------------------------------
# Sequential insert/update (the merge path). Applied strictly in log order.
# --------------------------------------------------------------------------
def _locate(table: CLHT, key):
    """Walk the chain of ``key``: returns (match_b, match_s, empty_b,
    empty_s, tail_b) with -1 for 'not found'. Traced, single key."""
    b0 = bucket_of(key[None], table.num_buckets)[0]

    def body(state, _):
        cur, mb, ms, eb, es, tail, active = state
        row = table.keys[cur]                          # (SLOTS,)
        is_match = (row == key) & active
        is_empty = (row == EMPTY) & active
        slot_ids = jnp.arange(SLOTS, dtype=jnp.int32)
        first_match = jnp.where(is_match.any(),
                                jnp.min(jnp.where(is_match, slot_ids, SLOTS)),
                                -1)
        first_empty = jnp.where(is_empty.any(),
                                jnp.min(jnp.where(is_empty, slot_ids, SLOTS)),
                                -1)
        new_mb = jnp.where((mb == -1) & (first_match >= 0), cur, mb)
        new_ms = jnp.where((mb == -1) & (first_match >= 0), first_match, ms)
        new_eb = jnp.where((eb == -1) & (first_empty >= 0), cur, eb)
        new_es = jnp.where((eb == -1) & (first_empty >= 0), first_empty, es)
        tail = jnp.where(active, cur, tail)
        nxt = table.nxt[cur]
        active = active & (nxt != EMPTY)
        cur = jnp.where(active, nxt, cur)
        return (cur, new_mb, new_ms, new_eb, new_es, tail, active), None

    init = (b0, jnp.int32(-1), jnp.int32(-1), jnp.int32(-1), jnp.int32(-1),
            b0, jnp.bool_(True))
    (cur, mb, ms, eb, es, tail, _), _ = jax.lax.scan(body, init, None,
                                                     length=MAX_CHAIN)
    return mb, ms, eb, es, tail


def _insert_one(table: CLHT, key, ptr, live_delta):
    """Insert/update one entry; returns (table, old_ptr, ok).

    ``live_delta`` accumulates +1 for a fresh insert, 0 for update (the
    per-segment GC counters in log.py consume old_ptr)."""
    mb, ms, eb, es, tail = _locate(table, key)
    is_update = mb >= 0
    has_empty = eb >= 0
    can_overflow = table.overflow_head < table.total_buckets

    # target bucket/slot: update in place > fill empty > new overflow bucket
    tb = jnp.where(is_update, mb, jnp.where(has_empty, eb,
                                            table.overflow_head))
    ts = jnp.where(is_update, ms, jnp.where(has_empty, es, 0))
    ok = is_update | has_empty | can_overflow

    old_ptr = jnp.where(is_update, table.ptrs[tb, ts], EMPTY)
    keys = jnp.where(ok, table.keys.at[tb, ts].set(key), table.keys)
    ptrs = jnp.where(ok, table.ptrs.at[tb, ts].set(ptr), table.ptrs)
    link = (~is_update) & (~has_empty) & can_overflow
    nxt = jnp.where(link, table.nxt.at[tail].set(table.overflow_head),
                    table.nxt)
    head = table.overflow_head + link.astype(jnp.int32)
    new = CLHT(keys=keys, ptrs=ptrs, nxt=nxt, overflow_head=head,
               num_buckets=table.num_buckets)
    live_delta = live_delta + jnp.where(ok & ~is_update, 1, 0)
    return new, old_ptr, ok, live_delta


@jax.jit
def clht_insert(table: CLHT, keys: jax.Array, ptrs: jax.Array,
                mask: jax.Array | None = None):
    """Merge a batch of (key, ptr) entries *in order* (paper: 'merges the
    write operations in a log segment in order into the metadata index').

    Returns (table, old_ptrs, ok, num_new). ``old_ptrs[i]`` is the value
    pointer replaced by entry i (-1 if it was a fresh insert) -- used for
    log-segment GC accounting."""
    if mask is None:
        mask = jnp.ones(keys.shape, bool)

    def step(carry, kpm):
        table, live = carry
        key, ptr, m = kpm
        def do(args):
            t, lv = args
            t2, old, ok, lv2 = _insert_one(t, key, ptr, lv)
            return t2, old, ok, lv2
        def skip(args):
            t, lv = args
            return t, EMPTY, jnp.bool_(False), lv
        table, old, ok, live = jax.lax.cond(m, do, skip, (table, live))
        return (table, live), (old, ok)

    (table, live), (old_ptrs, ok) = jax.lax.scan(
        step, (table, jnp.int32(0)), (keys, ptrs, mask))
    return table, old_ptrs, ok, live


@jax.jit
def clht_delete(table: CLHT, keys: jax.Array,
                mask: jax.Array | None = None):
    """Delete a batch of keys (in order). Returns (table, old_ptrs, found)."""
    if mask is None:
        mask = jnp.ones(keys.shape, bool)

    def step(table, km):
        key, m = km
        mb, ms, _, _, _ = _locate(table, key)
        hit = (mb >= 0) & m
        tb = jnp.maximum(mb, 0)
        old = jnp.where(hit, table.ptrs[tb, ms], EMPTY)
        keys_arr = jnp.where(hit, table.keys.at[tb, ms].set(EMPTY),
                             table.keys)
        ptrs_arr = jnp.where(hit, table.ptrs.at[tb, ms].set(EMPTY),
                             table.ptrs)
        return CLHT(keys=keys_arr, ptrs=ptrs_arr, nxt=table.nxt,
                    overflow_head=table.overflow_head,
                    num_buckets=table.num_buckets), (old, hit)

    table, (old_ptrs, found) = jax.lax.scan(step, table, (keys, mask))
    return table, old_ptrs, found


# ==========================================================================
# Numpy mirror with identical layout/semantics (per-op simulator plane).
# ==========================================================================
class NumpyCLHT:
    """Same structure, imperatively updated: fast per-op path for the
    cluster simulator. ``probes`` returned by lookup equals the number of
    bucket rows (cache lines / one-sided reads) touched."""

    def __init__(self, num_buckets: int, overflow_buckets: int | None = None,
                 telemetry: Telemetry | None = None):
        assert num_buckets & (num_buckets - 1) == 0
        # the owning pool's (merge_plan.* coverage counts)
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        if overflow_buckets is None:
            overflow_buckets = max(num_buckets // 2, 8)
        total = num_buckets + overflow_buckets
        self.num_buckets = num_buckets
        self.keys = np.full((total, SLOTS), -1, np.int64)
        self.ptrs = np.full((total, SLOTS), -1, np.int64)
        self.nxt = np.full((total,), -1, np.int64)
        self.overflow_head = num_buckets
        self.size = 0
        # bumped on every mutation: batched probes prefetched against one
        # version are only valid while the version is unchanged
        self.version = 0

    def _bucket(self, key: int) -> int:
        m = 0xFFFFFFFF
        x = key & m
        x = ((x ^ (x >> 16)) * 0x7FEB352D) & m
        x = ((x ^ (x >> 15)) * 0x846CA68B) & m
        x = (x ^ (x >> 16)) & m
        return x & (self.num_buckets - 1)

    def lookup(self, key: int):
        """-> (ptr or None, probes)"""
        b = self._bucket(key)
        probes = 0
        for _ in range(MAX_CHAIN):
            probes += 1
            for s in range(SLOTS):
                if self.keys[b, s] == key:
                    return int(self.ptrs[b, s]), probes
            if self.nxt[b] == -1:
                return None, probes
            b = int(self.nxt[b])
        return None, probes

    def _bucket_batch(self, keys: np.ndarray) -> np.ndarray:
        """Vectorized ``_bucket``: identical mixing per element (the
        single shared implementation lives with the merge planner)."""
        return _merge_bucket_batch(keys, self.num_buckets)

    def lookup_batch(self, keys: np.ndarray):
        """Vectorized chain walk over a batch of keys.

        -> (ptrs, probes): int64 arrays; ptr == -1 where absent. Matches
        ``lookup`` per element (the batched data plane's index gather).
        """
        keys = np.asarray(keys, dtype=np.int64)
        n = keys.shape[0]
        cur = self._bucket_batch(keys)
        ptrs = np.full(n, -1, np.int64)
        probes = np.zeros(n, np.int64)
        active = np.ones(n, bool)
        for _ in range(MAX_CHAIN):
            if not active.any():
                break
            rows_k = self.keys[cur]                     # (n, SLOTS)
            hit = (rows_k == keys[:, None]) & active[:, None]
            hit_any = hit.any(axis=1)
            probes += active
            if hit_any.any():
                rows_p = self.ptrs[cur]
                # first matching slot, as in the scalar walk (insert keeps
                # keys unique per chain, so at most one slot matches)
                slot = np.argmax(hit, axis=1)
                ptrs[hit_any] = rows_p[np.arange(n), slot][hit_any]
            nxt = self.nxt[cur]
            active = active & ~hit_any & (nxt != -1)
            cur = np.where(active, nxt, cur)
        return ptrs, probes

    def apply_merge_plan(self, plan) -> None:
        """Apply one :class:`~repro.core.transition.MergeWindowPlan` in
        bulk: in-place final-pointer scatters for present keys, slot
        claims for absent keys (primary-row or chain empties resolved by
        the planner, claim order proven exact), one version bump per
        live entry -- exactly the scalar insert sequence's evolution."""
        if plan.upd_rows.size:
            self.ptrs[plan.upd_rows, plan.upd_slots] = plan.upd_ptrs
        if plan.n_new:
            self.keys[plan.new_rows, plan.new_slots] = plan.new_keys
            self.ptrs[plan.new_rows, plan.new_slots] = plan.new_ptrs
            self.size += plan.n_new
        self.version += plan.n_index

    def insert_batch(self, keys: np.ndarray, ptrs: np.ndarray):
        """Planned sequential insert: element-wise identical to calling
        ``insert`` per (key, ptr) in order -- same superseded pointers
        (including within-batch duplicate chains), same slot placement,
        same overflow allocation order.

        The batch runs through the planned merge plane
        (transition.plan_merge_window -> apply_merge_plan): one
        vectorized sweep resolves grouped bucket targets, per-bucket
        slot assignment and old-pointer supersession; entries past a
        plan's self-truncation point (a bucket whose chain must grow)
        replay through the scalar insert in order before re-planning.

        -> (old_ptrs, ok, grown_buckets): old_ptrs[i] is the pointer
        entry i superseded (-1 for a fresh insert), ok[i] mirrors the
        scalar ok flag, and grown_buckets lists primary buckets whose
        chains grew -- a probe-count hazard for concurrently prefetched
        lookups of other keys in those chains."""
        keys = np.asarray(keys, dtype=np.int64)
        ptrs = np.asarray(ptrs, dtype=np.int64)
        n = keys.shape[0]
        old = np.full(n, -1, np.int64)
        ok = np.ones(n, bool)
        grown: list[int] = []
        counts = self.telemetry.counts
        i = 0
        while i < n:
            plan = plan_merge_window(self, keys[i:], ptrs[i:],
                                     tombstones=False)
            if plan is None:
                head0 = self.overflow_head
                o, okk = self.insert(int(keys[i]), int(ptrs[i]))
                if self.overflow_head != head0:
                    grown.append(int(self._bucket(int(keys[i]))))
                if o is not None:
                    old[i] = o
                ok[i] = okk
                counts["merge_plan.replayed_windows"] += 1
                counts["merge_plan.replayed_entries"] += 1
                i += 1
                continue
            self.apply_merge_plan(plan)
            old[i:i + plan.ops] = plan.old
            counts["merge_plan.planned_windows"] += 1
            counts["merge_plan.planned_entries"] += plan.ops
            i += plan.ops
        return old, ok, grown

    def insert(self, key: int, ptr: int):
        """-> (old_ptr or None, ok)"""
        b = self._bucket(key)
        empty = None
        tail = b
        for _ in range(MAX_CHAIN):
            for s in range(SLOTS):
                if self.keys[b, s] == key:
                    old = int(self.ptrs[b, s])
                    self.ptrs[b, s] = ptr
                    self.version += 1
                    return old, True
                if empty is None and self.keys[b, s] == -1:
                    empty = (b, s)
            tail = b
            if self.nxt[b] == -1:
                break
            b = int(self.nxt[b])
        if empty is not None:
            eb, es = empty
            self.keys[eb, es] = key
            self.ptrs[eb, es] = ptr
            self.size += 1
            self.version += 1
            return None, True
        if self.overflow_head < self.keys.shape[0]:
            nb = self.overflow_head
            self.overflow_head += 1
            self.nxt[tail] = nb
            self.keys[nb, 0] = key
            self.ptrs[nb, 0] = ptr
            self.size += 1
            self.version += 1
            return None, True
        return None, False  # overflow region exhausted

    def delete(self, key: int):
        b = self._bucket(key)
        for _ in range(MAX_CHAIN):
            for s in range(SLOTS):
                if self.keys[b, s] == key:
                    old = int(self.ptrs[b, s])
                    self.keys[b, s] = -1
                    self.ptrs[b, s] = -1
                    self.size -= 1
                    self.version += 1
                    return old, True
            if self.nxt[b] == -1:
                return None, False
            b = int(self.nxt[b])
        return None, False
