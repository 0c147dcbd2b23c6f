"""Compile the store's device programs for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a
described ``v5e:2x2`` topology and refuses what the chip would refuse
(unaligned blocks, over-budget VMEM, programs that do not fit HBM).
Sizes are the real ones: the compiled batch executor at the slot count
``chip_smoke.py`` serves, and the Pallas kernels over a 2^20-bucket
index and a heap of 1 KB value rows.  Every Pallas op is forced to
``interpret=False``, and each test checks that a Mosaic kernel
(``tpu_custom_call``) is in the program -- an interpreted kernel would
compile too, but is not what runs on the chip.

The topology is described inside a module fixture, never at import,
so collecting this file loads no TPU library.  The tests skip only
where the TPU compiler (libtpu) is not installed.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.clht import SLOTS, CLHT
from repro.core.jit_engine import W_MAX
from repro.core.log import LogSegment, ValueHeap
from repro.kernels import batch_executor as be
from repro.kernels.batch_executor.ops import compiled_window
from repro.kernels.cache_transition import cache_transition
from repro.kernels.clht_probe import (clht_probe, kvs_lookup,
                                      kvs_lookup_fused)
from repro.kernels.log_merge import (log_append_merge, log_merge,
                                     merge_segment_fast)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NB = 1 << 20                    # index primary buckets
TB = NB + NB // 2               # + the overflow region (clht_init)
HEAP_ROWS = 1 << 20
ROW = 1024 // 4                 # one 1 KB value per int32 heap row
BATCH = W_MAX
HBM_BYTES = 16 * 10 ** 9        # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    # only a missing TPU compiler skips; any other failure to describe
    # the chip fails the tests
    pytest.importorskip("libtpu", reason="no TPU compiler (libtpu)")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip):
    return lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < HBM_BYTES, m
    return m


@pytest.fixture(scope="module")
def smoke_window(one_chip):
    """The served executor at the smoke's slot count (2^22 keys -> a
    2^22-slot cache per KN) and W_MAX ops, with the state donated;
    returns (slot count, compiled program)."""
    sys.path.insert(0, REPO)
    import chip_smoke
    S = _spec(one_chip)
    nslots = chip_smoke.DEFAULT_KEYS
    state = tuple(S((nslots,)) for _ in range(6)) + (
        S((be.CNT_HIST_MAX + 1,)), S((be.NUM_REGS,)))
    w = S((W_MAX,))
    lowered = compiled_window("tpu").lower(
        state, w, w, w, w, w, w, S(()), S(()), S(()), S((be.TABLE_N,)))
    return nslots, lowered.compile()


def test_fused_window_compiles_at_smoke_slots(smoke_window):
    nslots, compiled = smoke_window
    m = _fits(compiled)
    # donation took: the returned state reuses the passed buffers
    assert m.alias_size_in_bytes >= 6 * 4 * nslots, m


def _computations(hlo):
    """{name: instruction lines} of an HLO module's text, and the
    entry computation's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _while_bodies(lines):
    return [re.search(r"body=%?([\w.\-]+)", l).group(1)
            for l in lines if " while(" in l]


def _elements(line):
    """Element count of an instruction's result (a tuple's first)."""
    shape = re.search(r"= \(?\w+\[([\d,]*)\]", line)
    n = 1
    for d in (shape.group(1).split(",") if shape else ["0"]):
        n *= int(d) if d else 1
    return n


def _ops(lines, op):
    return [l for l in lines if re.search(r"\b%s\(" % op, l)]


def _reached(comps, name):
    """``name`` and every computation it calls, except loop bodies and
    conditions (their own instructions are counted apart)."""
    seen, todo = set(), [name]
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            for l in comps[name]:
                todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", l)
    return [l for name in seen for l in comps[name]]


def _shape(line):
    """An instruction's result shape text, e.g. ``s32[4,2048]``."""
    m = re.search(r"= (\w+\[[\d,]*\])", line)
    return m.group(1) if m else None


def test_fused_window_structure_on_tpu(smoke_window):
    """The TPU-compiled executor keeps the victim trees' costs flat in
    the slot count and each op to a few launches: each op's loop body
    and each make-space iteration launch a bounded number of fusions
    and in-place writes; neither updates a register array (the
    registers are scalar carries) or writes a tree's block array more
    than once (one column per tree update, and the op's own removal
    from the trees lives in make-space); neither body scatters into or
    copies a whole state or tree array (a TPU scatter passes over its
    whole operand; a copy would come from a branch, from make-space
    reading what it does not write, or from hits re-reading the
    pre-update ptr); the entry builds the trees from contiguous blocks,
    with no large gather."""
    nslots, compiled = smoke_window
    comps, entry = _computations(compiled.as_text())
    outer = [b for b in _while_bodies(comps[entry])
             if _while_bodies(comps[b])]
    assert len(outer) == 1, _while_bodies(comps[entry])
    inner = _while_bodies(comps[outer[0]])
    assert len(inner) == 1, inner
    big = 1 << 20
    nblocks = nslots >> (nslots.bit_length() // 2)
    for body, most in ((outer[0], 30), (inner[0], 14)):
        lines = comps[body]
        writes = _ops(lines, "fusion") + _ops(lines, "dynamic-update-slice")
        assert len(writes) <= most, (body, len(writes))
        assert not [l for l in writes
                    if _shape(l) == "s32[%d]" % be.NUM_REGS], body
        for lanes in (4, 2):       # the LRU and the LFU tree
            block = [l for l in writes
                     if _shape(l) == "s32[%d,%d]" % (lanes, nblocks)]
            assert len(block) == 1, (body, block)
        assert not _ops(lines, "conditional"), body
        assert not [l for l in _ops(lines, "copy(-start)?")
                    if _elements(l) >= big], body
        assert not [l for l in _ops(_reached(comps, body), "scatter")
                    if _elements(l) >= big], body
    gathers = [l for l in _ops(_reached(comps, entry), "gather")
               if _elements(l) >= big]
    assert not gathers, gathers


def _tables(S):
    table = CLHT(keys=S((TB, SLOTS)), ptrs=S((TB, SLOTS)), nxt=S((TB,)),
                 overflow_head=S(()), num_buckets=NB)
    heap = ValueHeap(data=S((HEAP_ROWS, ROW)), head=S(()))
    seg = LogSegment(keys=S((BATCH,)), ptrs=S((BATCH,)),
                     seal=S((BATCH,)), count=S(()), merged=S(()))
    return table, heap, seg


# (name, op, argument builder): the four Pallas kernels and the jitted
# store ops that wrap them
KERNELS = [
    ("clht_probe", clht_probe,
     lambda S, t, h, g: (S((TB, 128)), S((BATCH,)), S((BATCH,)))),
    ("kvs_lookup_fused", kvs_lookup_fused,
     lambda S, t, h, g: (S((TB, 128)), S((HEAP_ROWS, ROW)),
                         S((BATCH,)), S((BATCH,)))),
    ("kvs_lookup", kvs_lookup, lambda S, t, h, g: (t, h, S((BATCH,)))),
    # value rows narrower than a lane tile: the heap is padded to whole
    # (8, 128) tiles (Mosaic refuses a 4-lane slice)
    ("kvs_lookup_narrow_rows", kvs_lookup,
     lambda S, t, h, g: (t, ValueHeap(data=S((1000, 4)), head=S(())),
                         S((BATCH,)))),
    ("log_merge", log_merge,
     lambda S, t, h, g: (S((TB, 128)), S((BATCH,)), S((BATCH,)),
                         S((BATCH,)))),
    ("merge_segment_fast", merge_segment_fast,
     lambda S, t, h, g: (t, g)),
    ("log_append_merge", log_append_merge,
     lambda S, t, h, g: (t, g, h, S((1024,)), S((1024, ROW)))),
    ("cache_transition",
     lambda *a, interpret: cache_transition(*a, cap=1 << 27,
                                            interpret=interpret),
     lambda S, t, h, g: (S((BATCH, 8)), S((4096,)), S(()), S(()))),
]


@pytest.mark.parametrize("name,op,args", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_pallas_kernel_compiles_for_v5e(one_chip, name, op, args):
    S = _spec(one_chip)
    lowered = jax.jit(lambda *a: op(*a, interpret=False)).lower(
        *args(S, *_tables(S)))
    assert "tpu_custom_call" in lowered.as_text(), name
    _fits(lowered.compile())
