"""Allocated edges by endpoint: which edge ids have one of these slots
as their source or as their target.

``ArrayShadowGraph`` keeps its references as flat COO arrays
(``edge_src``, ``edge_dst``, ``edge_weight``; an id is allocated iff its
weight is nonzero) and finds an exact pair through ``edge_of``.  The
sweep asks the other question: every allocated edge that touches a set
of dead slots, from either end.  A scan answers it in three passes over
the edge capacity, whatever died; this index answers it in time that
follows the dead and their references.

The shape is ``ops/slotmap.PackedSlotMap``'s, sorted bulk plus churn
overlay, kept as a few sorted runs so that a query late in a long run
costs what an early one does:

- a **run** is two sorted int64 arrays, one per direction, of
  ``endpoint << 32 | edge id``.  The edges of slot ``s`` are the span
  between ``searchsorted(s << 32)`` and ``searchsorted((s + 1) << 32)``,
  and the id is the key's low half: no second array, no gather to find
  the span.  (One packed array sorts twice as fast as a stable argsort
  of the endpoints, and needles of the haystack's dtype keep numpy from
  converting the haystack on every call.)
- the **overlay** is the ids allocated since the last run was sealed,
  append-only, examined whole by every query.  Past ``overlay_bound``
  the next query seals it into a run of its own and merges runs
  binary-counter fashion (a run is merged into its predecessor while
  that is no more than twice as long), so there are O(log) runs and an
  id is re-sorted O(log) times over its life.
- deletion is **lazy**: nothing is told of a freed edge.  Every
  candidate is checked against the CURRENT arrays, which is where a
  freed id, or one reused under other endpoints, drops out; a merge
  purges the entries that no longer hold.
- allocations that **outrun** the overlay (a bulk load: more than half
  of what is indexed) drop the index; the next query builds one run
  from the arrays, the O(E log E) that load has paid several times over
  in hashing its keys.

Per-slot linked lists in arrays would make a hub's death one numpy
round per reference; sorted spans make it one span.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from .i64map import IntStack

_LOW = np.int64(0xFFFFFFFF)
_NEXT = np.int64(1) << 32


def _keys_of(endpoint: np.ndarray, eids: np.ndarray) -> np.ndarray:
    """``endpoint[eid] << 32 | eid`` of ``eids``, sorted."""
    keys = endpoint[eids].astype(np.int64)
    keys <<= 32
    keys |= eids
    keys.sort()
    return keys


def _spans(keys: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """``keys[lo[i]:hi[i]]`` for every ``i``, concatenated."""
    count = hi - lo
    ends = np.cumsum(count)
    total = int(ends[-1]) if ends.size else 0
    if not total:
        return keys[:0]
    return keys[np.arange(total) + np.repeat(lo - (ends - count), count)]


class EndpointIndex:
    """Edge ids by either endpoint, over arrays it does not own: every
    call that reads them is handed the graph's current ``edge_src``,
    ``edge_dst`` and ``edge_weight``."""

    __slots__ = ("overlay_bound", "_runs", "_overlay", "_covers", "_room")

    def __init__(self, overlay_bound: int = 1 << 14):
        #: overlay entries a query examines whole before sealing them
        self.overlay_bound = overlay_bound
        #: (by source, by target) sorted packed keys, oldest run first
        self._runs: List[Tuple[np.ndarray, np.ndarray]] = []
        self._overlay = IntStack()
        #: runs + overlay name every allocated edge (an empty graph's
        #: empty index does); False once allocations outran the overlay
        self._covers = True
        #: overlay entries worth keeping (half of what the runs hold):
        #: past them a build from the arrays is the cheaper way to cover
        #: what was allocated
        self._room = overlay_bound

    @property
    def runs(self) -> int:
        return len(self._runs)

    def _set_runs(self, runs, covers: bool) -> None:
        self._runs = runs
        self._covers = covers
        indexed = sum(run[0].size for run in runs)
        self._room = max(self.overlay_bound, indexed >> 1)

    def _drop(self) -> None:
        self._overlay = IntStack()
        self._set_runs([], covers=False)

    def add(self, eid: int) -> None:
        """Edge ``eid`` was allocated."""
        if self._covers:
            if self._overlay.n >= self._room:
                self._drop()
            else:
                self._overlay.push(eid)

    def add_batch(self, eids: np.ndarray) -> None:
        """Edges ``eids`` were allocated."""
        if self._covers:
            if self._overlay.n + eids.size > self._room:
                self._drop()
            else:
                self._overlay.push_batch(eids)

    def _build(self, edge_src, edge_dst, edge_weight) -> None:
        alive = np.flatnonzero(edge_weight)
        self._overlay = IntStack()
        self._set_runs(
            [(_keys_of(edge_src, alive), _keys_of(edge_dst, alive))]
            if alive.size
            else [],
            covers=True,
        )

    def _seal(self, edge_src, edge_dst, edge_weight) -> None:
        """The overlay becomes the newest run, under the endpoints its
        ids have NOW (an id freed and allocated again since is in the
        overlay twice and in the run once)."""
        eids = np.unique(self._overlay.buf[: self._overlay.n])
        eids = eids[edge_weight[eids] != 0]
        self._overlay.n = 0
        run = (_keys_of(edge_src, eids), _keys_of(edge_dst, eids))
        runs = self._runs
        while runs and runs[-1][0].size <= 2 * run[0].size:
            older = runs.pop()
            run = tuple(
                self._merged(a, b, endpoint, edge_weight)
                for a, b, endpoint in (
                    (older[0], run[0], edge_src),
                    (older[1], run[1], edge_dst),
                )
            )
        if run[0].size or run[1].size:
            runs.append(run)
        self._set_runs(runs, covers=True)

    @staticmethod
    def _merged(a, b, endpoint, edge_weight) -> np.ndarray:
        """Two runs of one direction as one, less what no longer holds:
        ids freed, ids now under another endpoint, duplicates."""
        keys = np.concatenate([a, b])
        keys.sort(kind="stable")  # two sorted halves: one merge pass
        eids = keys & _LOW
        keep = (edge_weight[eids] != 0) & (endpoint[eids] == keys >> 32)
        keep[1:] &= keys[1:] != keys[:-1]
        return keys[keep]

    def incident(
        self,
        dead: np.ndarray,
        is_dead: np.ndarray,
        edge_src: np.ndarray,
        edge_dst: np.ndarray,
        edge_weight: np.ndarray,
    ) -> Tuple[np.ndarray, int]:
        """The allocated edges with an endpoint among ``dead`` (int64
        slots, ascending; ``is_dead`` is the same set as a bool vector
        over the slots), ascending: what
        ``(edge_weight != 0) & (is_dead[edge_src] | is_dead[edge_dst])``
        finds.  Beside them the candidates it examined to find them."""
        if not self._covers:
            self._build(edge_src, edge_dst, edge_weight)
        elif self._overlay.n > self.overlay_bound:
            self._seal(edge_src, edge_dst, edge_weight)
        first = dead << 32
        past = first + _NEXT
        found = [self._overlay.buf[: self._overlay.n]]
        for run in self._runs:
            for keys in run:
                span = _spans(
                    keys, np.searchsorted(keys, first), np.searchsorted(keys, past)
                )
                found.append(span & _LOW)
        cand = np.concatenate(found)
        holds = (edge_weight[cand] != 0) & (
            is_dead[edge_src[cand]] | is_dead[edge_dst[cand]]
        )
        return np.unique(cand[holds]), int(cand.size)
