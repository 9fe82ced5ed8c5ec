"""Packed-int64 slot maps: O(E) numpy storage for pair -> slot lookups.

The incremental layouts (ops/pallas_incremental.py, engines/crgc/mesh.py)
need a map from a live propagation pair to the slot holding it, so that a
later deletion can mask the slot in place.  A Python dict keyed by
(src, dst, kind) tuples costs hundreds of bytes per pair — multiple GB of
host objects at the 10M-actor/30M-pair target, and most of the rebuild
stall measured in BENCH_PACK_r02 was that dict's construction.

This map instead stores the bulk mapping as two sorted int64 numpy arrays
(16 bytes per pair) built vectorized at rebuild time; point lookups are a
binary search.  Mutations after the rebuild go through overlays: a small
Python insert dict, whose size is bounded by churn since the rebuild
(the layouts bound that by repacking), and a tombstone byte per bulk key.

Keys pack (src, dst, kind) into one int64: src in bits 32..62, dst in
bits 1..31, kind in bit 0 — node ids must stay below 2^31, which the
graph's int32 slot arrays already guarantee.  Values are whatever the
caller packs into an int64.
"""

from __future__ import annotations

from itertools import chain, repeat
from typing import Optional

import numpy as np


def pack_keys(src, dst, kind) -> np.ndarray:
    """Vectorized (src, dst, kind) -> int64 key."""
    return (
        (np.asarray(src, dtype=np.int64) << 32)
        | (np.asarray(dst, dtype=np.int64) << 1)
        | np.asarray(kind, dtype=np.int64)
    )


def pack_key(src: int, dst: int, kind: int) -> int:
    return (src << 32) | (dst << 1) | kind


def unpack_keys(karr: np.ndarray):
    """Vectorized int64 key -> (src, dst) arrays (kind = karr & 1)."""
    karr = np.asarray(karr, dtype=np.int64)
    return karr >> 32, (karr >> 1) & 0x7FFFFFFF


class PairLog:
    """A pair-transition log ``(insert?, src, dst, kind)`` as four int64
    columns, from the graph layer that writes it to the layout maps that
    fold it: no step between makes a Python object per pair.

    ``extend`` takes a batch's arrays as they are.  ``append`` takes one
    row as a 4-tuple and IS the ``list.append`` of ``staged`` (a scalar
    mutation pays what it paid when the log was a list); staged rows
    join the columns, in order, at the next ``extend`` or read.  A
    writer that bounds the log per row reads ``in_columns +
    len(staged)``, which is ``len()`` without a Python-level call."""

    __slots__ = ("append", "staged", "in_columns", "_cols")

    def __init__(self, rows=()):
        #: rows appended one by one since the columns were last written
        self.staged: list = list(rows)
        self.append = self.staged.append
        #: rows the columns hold
        self.in_columns = 0
        self._cols = np.empty((4, 1024), dtype=np.int64)

    @classmethod
    def of(cls, log) -> "PairLog":
        """The one door for the old form: ``log`` itself if it is a
        PairLog, else any sequence of 4-tuples turned into columns."""
        return log if isinstance(log, cls) else cls(log)

    def __len__(self) -> int:
        return self.in_columns + len(self.staged)

    @property
    def nbytes(self) -> int:
        """Of the rows held, not of the room kept for the next ones."""
        return 32 * len(self)

    def _room(self, k: int) -> int:
        """Make room for ``k`` more rows; returns where they start."""
        n = self.in_columns
        if n + k > self._cols.shape[1]:
            cols = np.empty(
                (4, 1 << int(n + k - 1).bit_length()), dtype=np.int64
            )
            cols[:, :n] = self._cols[:, :n]
            self._cols = cols
        self.in_columns = n + k
        return n

    def _flush(self) -> None:
        staged = self.staged
        if staged:
            # half the price of np.asarray(staged), which looks at the
            # type of every element of every tuple
            rows = np.fromiter(
                chain.from_iterable(staged), np.int64, 4 * len(staged)
            )
            n = self._room(len(staged))
            self._cols[:, n:self.in_columns] = rows.reshape(-1, 4).T
            staged.clear()  # in place: ``append`` is bound to this list

    def extend(self, insert: bool, srcs, dsts, kind: int) -> None:
        """``len(srcs)`` rows of one op and one kind, in order."""
        self._flush()
        n = self._room(len(srcs))
        rows = self._cols[:, n:self.in_columns]
        rows[0] = insert
        rows[1] = srcs
        rows[2] = dsts
        rows[3] = kind

    def columns(self):
        """``(insert, src, dst, kind)``: int64 views of the rows so far,
        valid until the log is next written or cleared."""
        self._flush()
        ins, src, dst, kind = self._cols[:, :self.in_columns]
        return ins, src, dst, kind

    def clear(self) -> None:
        self.staged.clear()
        self.in_columns = 0


def fold_log(ins, src, dst, kind):
    """Fold an alternating pair-transition log, given as its columns
    (``PairLog.columns``), into its net effect per packed key.

    A pair's transitions strictly alternate (graph layers only log
    dead<->live flips), so the net effect is determined by the first and
    last op.  Returns ``(removes, cond_removes, inserts, n_keys)``, the
    three as ascending int64 key arrays, ``n_keys`` the distinct keys:

    - ``removes``: first op is a remove — remove from the current home
      (absence is caller drift: count an anomaly);
    - ``cond_removes``: insert-first but remove-last — a net no-op for a
      fresh pair, but if the key was *already live* the insert was
      anomalous drift and the remove is real: remove and count an
      anomaly, matching the sequential scalar replay;
    - ``inserts``: last op is an insert — insert after the removals.
    """
    keys = pack_keys(src, dst, kind)
    order = np.argsort(keys, kind="stable")
    skeys = keys[order]
    head = np.ones(skeys.size, dtype=bool)
    head[1:] = skeys[1:] != skeys[:-1]
    at = np.flatnonzero(head)  # where each key's run of ops starts
    ukeys = skeys[at]
    first = ins[order[at]] != 0
    last = ins[order[np.append(at[1:], skeys.size) - 1]] != 0
    return ukeys[~first], ukeys[first & ~last], ukeys[last], ukeys.size


class PackedSlotMap:
    """int64 key -> int64 value map: sorted bulk arrays + churn overlays."""

    __slots__ = ("_keys", "_vals", "_dead", "_n_dead", "_extra")

    def __init__(
        self,
        keys: Optional[np.ndarray] = None,
        vals: Optional[np.ndarray] = None,
    ):
        if keys is None or keys.size == 0:
            self._keys = np.zeros(0, dtype=np.int64)
            self._vals = np.zeros(0, dtype=np.int64)
        else:
            order = np.argsort(keys)
            self._keys = np.ascontiguousarray(keys[order])
            self._vals = np.ascontiguousarray(vals[order])
        #: tombstones of the bulk keys, by position (a byte a key, where
        #: a set of the keys themselves cost ~70)
        self._dead = np.zeros(self._keys.size, dtype=bool)
        self._n_dead = 0
        self._extra: dict = {}  # post-rebuild inserts

    def __len__(self) -> int:
        return self._keys.size - self._n_dead + len(self._extra)

    def _bulk_find(self, key: int) -> int:
        """Index of ``key`` among the live bulk entries, or -1."""
        keys = self._keys
        i = int(np.searchsorted(keys, key))
        if i < keys.size and keys[i] == key and not self._dead[i]:
            return i
        return -1

    def __contains__(self, key: int) -> bool:
        return key in self._extra or self._bulk_find(key) >= 0

    def get(self, key: int) -> Optional[int]:
        val = self._extra.get(key)
        if val is not None:
            return val
        i = self._bulk_find(key)
        if i < 0:
            return None
        return int(self._vals[i])

    def add(self, key: int, val: int) -> None:
        """Insert; the key must not be present (callers check first).
        A tombstoned bulk key may be re-added — the overlay wins on
        lookup, and the tombstone keeps the stale bulk slot hidden."""
        self._extra[key] = val

    def add_batch(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """``add`` for a batch: none of ``keys`` may be present."""
        self._extra.update(zip(keys.tolist(), vals.tolist()))

    def pop(self, key: int) -> Optional[int]:
        val = self._extra.pop(key, None)
        if val is not None:
            return val
        i = self._bulk_find(key)
        if i < 0:
            return None
        self._dead[i] = True
        self._n_dead += 1
        return int(self._vals[i])

    # --------------------------------------------------------------- #
    # Batched point ops: one vectorized binary search for a whole churn
    # batch instead of a ~1us scalar searchsorted per key.
    # --------------------------------------------------------------- #

    def _lookup_batch(self, karr: np.ndarray, remove: bool) -> np.ndarray:
        # Precondition: the batch's keys ascend strictly, as fold_log
        # gives them.  Unique, because a duplicated bulk key would be
        # tombstoned once but resolved for every occurrence (a
        # double-free of the same column downstream); in order, because
        # the probes then walk the bulk keys front to back where a
        # batch in log order misses the cache at every level.
        assert (karr[1:] > karr[:-1]).all(), "batch keys must ascend strictly"
        extra = self._extra
        if extra:
            take = extra.pop if remove else extra.get
            out = np.fromiter(
                map(take, karr.tolist(), repeat(-1)), np.int64, karr.size
            )
            rest = np.flatnonzero(out < 0)
        else:
            out = np.full(karr.size, -1, dtype=np.int64)
            rest = np.arange(karr.size)
        if rest.size and self._keys.size:
            kq = karr[rest]
            pos = np.minimum(
                np.searchsorted(self._keys, kq), self._keys.size - 1
            )
            found = (self._keys[pos] == kq) & ~self._dead[pos]
            pos = pos[found]
            out[rest[found]] = self._vals[pos]
            if remove:
                self._dead[pos] = True
                self._n_dead += pos.size
        return out

    def pop_batch(self, karr: np.ndarray) -> np.ndarray:
        """Pop every key in ``karr`` (strictly ascending); returns int64
        values, -1 = absent."""
        return self._lookup_batch(np.asarray(karr, dtype=np.int64), remove=True)

    def get_batch(self, karr: np.ndarray) -> np.ndarray:
        """Look up every key in ``karr`` (strictly ascending); returns
        int64 values, -1 = absent."""
        return self._lookup_batch(np.asarray(karr, dtype=np.int64), remove=False)
