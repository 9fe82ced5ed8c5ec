"""The plain reference, linear in the pairs: CRGC's liveness trace as a
worklist over a CSR.

``reference.trace_marks`` advances every mark one hop per pass over whole
arrays: 12 passes on the power-law graph, one pass PER HOP on a chain
(500,000 passes of ~10 ms at ``chain-1m``).  This file computes the same
fixpoint, with the same semantics exactly (upstream
``ShadowGraph.java:201-289``, as ``reference.py`` states them): an actor
is a pseudoroot if it is in use, not halted, and a root, busy, holding
undelivered messages or not yet interned; marks spread from a marked,
non-halted actor along every reference with a positive count and to its
supervisor; only an actor in use is ever marked.

It visits every actor once and every pair once.  A wide frontier (the
power-law graph) is expanded a level at a time with numpy; a narrow one
(a chain) one actor at a time from a Python stack.  It imports nothing of
the program and nothing of ``reference.py`` but the flag constants;
``tests/test_chain.py`` holds it to ``reference.trace_marks`` on seeded
random graphs.
"""

from __future__ import annotations

import numpy as np

from reference import FLAG_BUSY, FLAG_HALTED, FLAG_IN_USE, FLAG_INTERNED, FLAG_ROOT

#: a frontier of at least this many actors is expanded with numpy
WIDE = 512


def successors(n, supervisor, edge_src, edge_dst, edge_weight):
    """CSR of the propagation pairs: ``dst[ptr[v]:ptr[v + 1]]`` is where a
    mark on ``v`` spreads, its positive-weight references and its
    supervisor."""
    live = edge_weight > 0
    child = np.nonzero(supervisor >= 0)[0]
    src = np.concatenate([edge_src[live].astype(np.int64), child])
    dst = np.concatenate([edge_dst[live].astype(np.int64), supervisor[child].astype(np.int64)])
    order = np.argsort(src, kind="stable")
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=ptr[1:])
    return ptr, dst[order]


def trace_marks(flags, recv_count, supervisor, edge_src, edge_dst, edge_weight) -> np.ndarray:
    """The mark fixpoint; returns bool[n]."""
    n = flags.shape[0]
    in_use = (flags & FLAG_IN_USE) != 0
    halted = (flags & FLAG_HALTED) != 0
    seed = (
        ((flags & FLAG_ROOT) != 0)
        | ((flags & FLAG_BUSY) != 0)
        | (recv_count != 0)
        | ((flags & FLAG_INTERNED) == 0)
    )
    mark = in_use & ~halted & seed
    ptr, dst = successors(n, supervisor, edge_src, edge_dst, edge_weight)
    # what may still be marked: in use and not marked yet
    open_ = in_use & ~mark
    ptr_l = dst_l = None
    work = np.nonzero(mark)[0]  # pseudoroots are not halted: all spread
    while work.size:
        if work.size >= WIDE:
            lo, counts = ptr[work], ptr[work + 1] - ptr[work]
            total = int(counts.sum())
            at = np.repeat(lo - np.cumsum(counts) + counts, counts) + np.arange(total)
            new = np.unique(dst[at])
            new = new[open_[new]]
            mark[new] = True
            open_[new] = False
            work = new[~halted[new]]
            continue
        if ptr_l is None:  # plain lists: scalar reads of an array are slow
            ptr_l, dst_l = ptr.tolist(), dst.tolist()
            spreads = (~halted).tolist()
        stack, reached = work.tolist(), []
        while stack and len(stack) < WIDE:
            v = stack.pop()
            for w in dst_l[ptr_l[v]:ptr_l[v + 1]]:
                if open_[w]:
                    open_[w] = False
                    reached.append(w)
                    if spreads[w]:
                        stack.append(w)
        mark[np.asarray(reached, np.int64)] = True
        work = np.asarray(stack, np.int64)
    return mark
