"""Packed mutator->collector entry plane.

The object ``Entry`` snapshot (state.py, reference: crgc/Entry.java:5-37)
is the differential oracle's plane and the multi-node plane (delta graphs
need refob identity).  This module is the single-node hot path the SURVEY
§7 design calls for: a flush writes one packed int64 row into a
per-thread ring buffer, and the collector's drain is array slicing — no
per-entry Python object walk anywhere on the Bookkeeper thread (the
system's single fold bottleneck; the mutator threads, which scale with
the dispatcher pool, pay the flattening instead).

Row layout (width = 4 + 5*E, E = entry-field-size, -1 = empty field):

    col 0          seq       global flush order (busy/root bits are
                             last-writer-wins per actor, so cross-thread
                             total order must be restorable at the fold)
    col 1          self uid  ``ActorCell.uid`` (dense per system)
    col 2          bits      bit0 busy, bit1 root
    col 3          recv      messages received this period
    cols 4..4+2E   E created (owner_uid, target_uid) pairs
    next E         E spawned child uids
    next 2E        E updated (target_uid, packed refob info) pairs

Uids, not slots: slot assignment stays single-writer on the collector
(ArrayShadowGraph.merge_packed maps uids through a dense ``uid -> slot``
array and interns only unseen uids).  The plane's ``uid_strong`` dict
pins every cell named by an in-flight row so the collector can always
resolve it; pins live until the actor's slot is swept
(ArrayShadowGraph._free_slots_batch pops them) — interning alone does
not release a pin, it only makes future lookups bypass it.

Foreign actors.  An actor whose cell lives in another process (a mutator
process that ships this collector its entry flushes: the JVM actor
systems of the north star) has no ``ActorCell`` here: the collector
knows it by uid alone and answers with the uids to stop.  Such a uid is
the mutator side's own dense number, carried in a row with
``FOREIGN_BIT`` set (:func:`foreign`), so one row may name local and
foreign actors side by side and every other rule of the layout holds.
A foreign uid names one actor for good: it is never pinned, never
resolved, and never handed out again after its actor was swept
(ArrayShadowGraph keeps a tombstone for it).  :meth:`PackedPlane.
write_foreign` takes a whole block of rows in the mutator side's plain
uids, tags and stamps them, and publishes them at once; the sweep hands
the uids to stop to the sink the engine exposes
(``CRGC.set_foreign_sink``).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, Optional

import numpy as np

ROW_FIXED = 4  # seq, self uid, busy/root bits, recv count

#: set in a row's uid field that names a foreign actor; far above any
#: ``ActorCell.uid`` a system's counter reaches, and still >= 0, so the
#: fold's "field in use" tests read it like any uid
FOREIGN_BIT = 1 << 62


def row_width(entry_field_size: int) -> int:
    return ROW_FIXED + 5 * entry_field_size


def foreign(uid):
    """The row code of foreign uid(s) ``uid`` (an int or an array)."""
    return uid | FOREIGN_BIT


def uid_columns(entry_field_size: int) -> np.ndarray:
    """The columns of a row that hold uids: self, the created pairs,
    the spawned children and the target of each updated pair (its other
    half is the packed refob info, which is no uid)."""
    E = entry_field_size
    return np.concatenate([
        [1],
        np.arange(ROW_FIXED, ROW_FIXED + 3 * E),
        np.arange(ROW_FIXED + 3 * E, ROW_FIXED + 5 * E, 2),
    ])


class PackedRing:
    """SPSC ring of packed rows: one writer (the mutator thread that owns
    it), one reader (the Bookkeeper).  The writer's fast path takes no
    lock — under the GIL the row store completes before the ``w``
    publish, and the reader never reads at or past ``w``.  The lock
    serializes only the two buffer-wide operations: writer grow and
    reader drain."""

    __slots__ = ("buf", "cap", "r", "w", "lock")

    def __init__(self, width: int, cap: int = 1 << 12):
        assert cap & (cap - 1) == 0
        self.buf = np.empty((cap, width), dtype=np.int64)
        self.cap = cap
        self.r = 0  # read cursor (reader-owned), monotonic
        self.w = 0  # write cursor (writer-owned), monotonic
        self.lock = threading.Lock()

    def begin(self) -> np.ndarray:
        """The next row's buffer view; the reader cannot see it until
        :meth:`commit`.  Stale contents from a previous lap — the caller
        must fill every column."""
        if self.w - self.r >= self.cap:
            # A stale ``r`` read only over-estimates fullness (r is
            # monotonic), so a spurious grow is possible but an
            # overwrite of unread rows is not.
            with self.lock:
                self._grow()
        return self.buf[self.w & (self.cap - 1)]

    def commit(self) -> None:
        self.w += 1

    def extend(self, rows: np.ndarray) -> None:
        """Write a block of rows and publish them at once: the reader
        sees all of the block or none of it."""
        k = rows.shape[0]
        if self.w - self.r + k > self.cap:
            with self.lock:
                self._grow(self.w - self.r + k)
        cap = self.cap
        i0 = self.w & (cap - 1)
        head = min(k, cap - i0)
        self.buf[i0 : i0 + head] = rows[:head]
        if head < k:  # wraps
            self.buf[: k - head] = rows[head:]
        self.w += k

    def _grow(self, need: int = 0) -> None:
        # Reader excluded by the lock; relinearize [r, w) from 0, into
        # the power of two that holds ``need`` rows (at least double).
        cap, r, w = self.cap, self.r, self.w
        new_cap = cap * 2
        while new_cap < need:
            new_cap *= 2
        new = np.empty((new_cap, self.buf.shape[1]), dtype=np.int64)
        idx = (np.arange(r, w) & (cap - 1))
        count = w - r
        new[:count] = self.buf[idx]
        self.buf = new
        self.cap = new_cap
        self.r = 0
        self.w = count

    def drain(self) -> Optional[np.ndarray]:
        """Copy out all committed rows (None if empty)."""
        with self.lock:
            r, w = self.r, self.w
            if r == w:
                return None
            cap = self.cap
            i0 = r & (cap - 1)
            i1 = w & (cap - 1)
            if i0 < i1:
                out = self.buf[i0:i1].copy()
            else:  # wrapped (or exactly full)
                out = np.concatenate([self.buf[i0:], self.buf[:i1]])
            self.r = w
            return out


class PackedPlane:
    """Per-engine bundle: one ring per mutator thread, the global flush
    sequence, and the strong uid->cell pin set."""

    def __init__(self, entry_field_size: int):
        self.entry_field_size = entry_field_size
        self.width = row_width(entry_field_size)
        #: itertools.count.__next__ is a single C call — atomic under
        #: the GIL, so concurrent flushes get distinct ordered stamps.
        self._seq = itertools.count()
        self._uid_cols = uid_columns(entry_field_size)
        #: cells named by in-flight rows; dict.setdefault / .pop are
        #: individually atomic under the GIL.  Pins persist until the
        #: collector SWEEPS the actor's slot (_free_slots_batch), not
        #: until intern: the graph's cells[] also pins an interned cell,
        #: so the extra pin is redundant but harmless, and releasing it
        #: only at sweep keeps the release single-writer.
        self.uid_strong: Dict[int, object] = {}
        self._rings: Dict[int, PackedRing] = {}
        #: rows drained past the last cut, folded by the next drain
        self._held: Optional[np.ndarray] = None
        self._lock = threading.Lock()
        self._tl = threading.local()
        #: set while a wake profiler is attached (telemetry.Telemetry):
        #: writers then leave the ``perf_counter`` of the first write
        #: since the collector last looked in :attr:`first_write`.  The
        #: collector takes and clears it BEFORE its drain, so a row that
        #: drain holds back (stamped after the cut, so written after the
        #: clearing) has its time on the clock of the drain that takes it.
        self.timed = False
        self.first_write: Optional[float] = None

    def next_seq(self) -> int:
        return next(self._seq)

    def write_foreign(self, rows: np.ndarray) -> None:
        """Hand over a block of rows whose every uid is foreign: the
        flushes a mutator process shipped, in its own plain uids (-1 =
        empty field), in flush order.  The block is tagged
        (``FOREIGN_BIT`` on every uid field in use), stamped with
        consecutive flush stamps in its order (column 0 is overwritten)
        and published to the calling thread's ring at once, so a drain
        takes all of it or none.  ``rows`` is written in place."""
        k = rows.shape[0]
        if not k:
            return
        handed = time.perf_counter() if self.timed else None
        cols = self._uid_cols
        uids = rows[:, cols]
        np.bitwise_or(uids, FOREIGN_BIT, out=uids, where=uids >= 0)
        rows[:, cols] = uids
        # islice over the C counter takes no Python step per stamp, so
        # no other thread's flush lands inside the block's stamps
        rows[:, 0] = np.fromiter(itertools.islice(self._seq, k), np.int64, k)
        self.ring().extend(rows)
        if handed is not None and self.first_write is None:
            self.first_write = handed  # once published, as a flush's

    def ring(self) -> PackedRing:
        r = getattr(self._tl, "ring", None)
        if r is None:
            r = PackedRing(self.width)
            with self._lock:
                # Keyed by ring identity, not thread id: thread-id reuse
                # after a worker dies must not alias two rings.  A dead
                # thread's drained-empty ring is a small, bounded leak
                # (the dispatcher pool is fixed-size).
                self._rings[id(r)] = r
            self._tl.ring = r
        return r

    def drain(self) -> Optional[np.ndarray]:
        """The committed rows stamped before this call, from every ring,
        unsorted (merge_packed restores flush order from the seq
        column).

        The rings are drained one after another, which alone is not a
        consistent cut: one actor flushes from more than one thread (its
        constructor runs on the spawner's thread, its batches on a
        dispatcher's), so a ring drained later can hold that actor's
        NEWER row while its older row — committed after the earlier
        ring's drain — is missed.  Folding the newer row alone breaks
        the per-actor FIFO that CRGC's soundness rests on (the older
        row carries the creator's ref: without it the actor looks
        unreferenced and is swept alive).  So a stamp is taken first
        and rows at or past it are held for the next drain: a row below
        the cut was stamped before the cut, its actor's older rows were
        committed before that, and every ring's drain comes after."""
        cut = self.next_seq()
        with self._lock:
            rings = list(self._rings.values())
        parts = [p for p in (r.drain() for r in rings) if p is not None]
        if self._held is not None:
            parts.append(self._held)
            self._held = None
        if not parts:
            return None
        rows = parts[0] if len(parts) == 1 else np.concatenate(parts)
        late = rows[:, 0] >= cut
        if late.any():
            self._held = rows[late]
            rows = rows[~late]
        return rows if rows.shape[0] else None
