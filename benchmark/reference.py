"""The plain reference: CRGC's liveness trace in numpy.

A copy of ``uigc_tpu/ops/trace.py`` (``trace_marks_np``,
``pseudoroots_np``) as it stood at PR 24, kept here because the program
may change and the yardstick may not.  It imports nothing of the program
and takes nothing the program has made.

Semantics (upstream ``ShadowGraph.java:201-289``): an actor is a
pseudoroot if it is in use, not halted, and a root, busy, holding
undelivered messages or not yet interned; marks spread from a marked,
non-halted actor along every reference with a positive count and to its
supervisor; garbage is what is in use and unmarked.
"""

from __future__ import annotations

import numpy as np

FLAG_ROOT = np.uint8(1)
FLAG_BUSY = np.uint8(2)
FLAG_INTERNED = np.uint8(4)
FLAG_LOCAL = np.uint8(8)
FLAG_HALTED = np.uint8(16)
FLAG_IN_USE = np.uint8(32)


def pseudoroots(flags: np.ndarray, recv_count: np.ndarray) -> np.ndarray:
    in_use = (flags & FLAG_IN_USE) != 0
    not_halted = (flags & FLAG_HALTED) == 0
    seed = (
        ((flags & FLAG_ROOT) != 0)
        | ((flags & FLAG_BUSY) != 0)
        | (recv_count != 0)
        | ((flags & FLAG_INTERNED) == 0)
    )
    return in_use & not_halted & seed


def trace_marks(flags, recv_count, supervisor, edge_src, edge_dst, edge_weight) -> np.ndarray:
    """The mark fixpoint; returns bool[n]."""
    in_use = (flags & FLAG_IN_USE) != 0
    halted = (flags & FLAG_HALTED) != 0
    mark = pseudoroots(flags, recv_count)

    live_edge = edge_weight > 0
    esrc = edge_src[live_edge]
    edst = edge_dst[live_edge]
    sup_src = np.nonzero(supervisor >= 0)[0]
    sup_dst = supervisor[sup_src]

    while True:
        active = mark & ~halted
        new_mark = mark.copy()
        if esrc.size:
            new_mark[edst[active[esrc]]] = True
        if sup_src.size:
            new_mark[sup_dst[active[sup_src]]] = True
        new_mark &= in_use
        if np.array_equal(new_mark, mark):
            return mark
        mark = new_mark


def garbage(flags, marks) -> np.ndarray:
    return ((flags & FLAG_IN_USE) != 0) & ~marks
