"""Fused compiled batch executor (jitted window state machine).

``fused_window`` (ops.py) is the single-dispatch compiled engine;
``fused_window_ref`` (ref.py) is its pure-numpy oracle defining the
per-op contract bit-for-bit.  ``build_promote_table`` discretizes the
float Eq. 1 promote decision into an integer threshold table so the
device program stays float-free; ``init_state`` packs host DAC arrays
into the donated device state tuple.
"""

from .ops import fused_window
from .ref import (CNT_HIST_MAX, CUT_EMA, CUT_NAMES, CUT_NONE,
                  CUT_PREFETCH, CUT_SEGCACHE, CUT_SPILL, CUT_TABLE,
                  EV_MISS_ABSENT, EV_MISS_FILL, EV_PROMOTE, EV_SHORTCUT_HIT,
                  EV_VALUE_HIT, EV_WRITE, NUM_REGS, OP_READ, OP_WRITE,
                  PM_ABSENT, PM_INVALID, R_CLOCK, R_DEMOTIONS,
                  R_EMA_DIRTY, R_EVICTIONS, R_MS_OPS, R_NSHORT, R_NVALS,
                  R_USED, R_ZSHORT, SHORTCUT_BYTES, TABLE_N,
                  VALUE_OVERHEAD_BYTES, build_promote_table,
                  fused_window_ref, init_state)

__all__ = [
    "fused_window", "fused_window_ref", "build_promote_table",
    "init_state", "CNT_HIST_MAX", "CUT_EMA", "CUT_NAMES", "CUT_NONE",
    "CUT_PREFETCH", "CUT_SEGCACHE", "CUT_SPILL", "CUT_TABLE",
    "EV_MISS_ABSENT", "EV_MISS_FILL", "EV_PROMOTE", "EV_SHORTCUT_HIT",
    "EV_VALUE_HIT", "EV_WRITE", "NUM_REGS", "OP_READ", "OP_WRITE",
    "PM_ABSENT", "PM_INVALID", "R_CLOCK", "R_DEMOTIONS", "R_EMA_DIRTY",
    "R_EVICTIONS", "R_MS_OPS", "R_NSHORT", "R_NVALS", "R_USED",
    "R_ZSHORT", "SHORTCUT_BYTES", "TABLE_N", "VALUE_OVERHEAD_BYTES",
]
