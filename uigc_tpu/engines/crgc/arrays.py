"""The array-oriented shadow graph: dense slots + COO edges.

The TPU-first redesign of the collector's detection structure.  Where the
reference holds a ``HashMap<ActorRef, Shadow>`` of pointer-linked shadows
(reference: ShadowGraph.java:9-21, Shadow.java:10-54), this implementation
interns actors into dense integer slots and keeps all node state in flat
numpy arrays — exactly the layout the trace kernels (ops/trace.py) consume
and the layout that ships to the device.  The fold (merge_entry) is a
host-side scatter; the trace runs either on the host (numpy) or on the
device (the decremental wake, ops/pallas_decremental.py), selected by
``use_device``.

Liveness semantics are identical to the oracle ShadowGraph; differential
tests (tests/test_trace_parity.py) drive both over the same entry streams
and compare verdicts — the reference author's own dual-graph technique
(reference: ShadowGraph.java:176-199).

One deliberate divergence: when a garbage node's slot is freed, all edges
incident to it are deleted.  The oracle (like the reference) leaves inert
negative-count edges keyed by dead Shadow objects in live actors' outgoing
maps (reference: ShadowGraph.java:64-73 never purges); those edges can
never propagate marks again (a positive edge to garbage is impossible), so
dropping them preserves liveness verdicts while keeping slots recyclable.
"""

from __future__ import annotations

import functools
import math
from contextlib import contextmanager
from typing import TYPE_CHECKING, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ...ops import pallas_incremental as pallas_incremental_kinds
from ...ops import trace as trace_ops
from ...ops.edgeindex import EndpointIndex
from ...ops.i64map import I64Map, IntStack
from ...ops.slotmap import PairLog
from ...utils import events
from ...utils.validation import require
from . import refob as refob_info
from .messages import StopMsg, WaveMsg
from .packed import FOREIGN_BIT
from .state import CrgcContext, Entry

if TYPE_CHECKING:  # pragma: no cover
    from ...runtime.cell import ActorCell
    from .refob import CrgcRefob

_F = trace_ops
_PAIR_EDGE = pallas_incremental_kinds.EDGE
_PAIR_SUP = pallas_incremental_kinds.SUP
#: ``_fuid_to_slot`` values below the slots: never seen / swept for good
_UNSEEN = -1
_SWEPT = -2
_NO_UIDS = np.empty(0, dtype=np.int64)
#: a death of more slots than this share of the edge capacity is swept
#: by the one scan over the edge arrays: the index costs about a
#: microsecond a dead slot with its references (binary searches over the
#: runs, then gathers), the scan ten to twenty nanoseconds an edge slot
_SCAN_SHARE = 64
#: a wake whose dirty-slot log holds more than ``capacity`` over this
#: uploads ``flags`` and ``recv_count`` whole instead of patching the
#: device's copies: whole costs by the capacity, a patch by the slot.
#: On the v5e's host (PERF.md section 6, PR 43) whole is 87-125 ms at
#: 2^24 slots and 2.5 ms at 2^17, 5-7 ns a slot; a patch 1.4 ms up to
#: 3k slots, then 43-55 ns a slot written (5.4 ms at 91k, 33 at 718k,
#: 61 at 1.4M, 125 at 2.7M; 2.1 ms at 21k of 2^17): they cross where a
#: sixth of the capacity was written.  The log counts a slot as often
#: as it was written, 1.3-1.4 times in the cells' wakes: an eighth.
_PATCH_SHARE = 8
#: the padded length of a patch of ``k`` slots: the layout's O(churn)
#: scatters' (a power of two, at least 4,096), for their reason: a
#: served round's few thousand slots share one program
_patch_pad = pallas_incremental_kinds._scatter_pad


@functools.lru_cache(maxsize=None)
def _patch_fn():
    """The jitted scatter that brings the device's ``flags`` and
    ``recv_count`` up to the host's: ``idx`` slots take the values given
    (absolute, not deltas), a slot out of range (the padding) is
    dropped, both arrays are donated.  One function for every graph: a
    program per capacity and padded length."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def patch(flags, recv, idx, fvals, rvals):
        return (
            flags.at[idx].set(fvals, mode="drop", indices_are_sorted=True),
            recv.at[idx].set(rvals, mode="drop", indices_are_sorted=True),
        )

    return patch


class _NodeLog:
    """The slots whose ``flags`` or ``recv_count`` the host wrote since
    the consumer last took them: the scalars of :meth:`ArrayShadowGraph.
    _touch` beside the index arrays of the batch sites (kept, not
    copied: a site hands over an array it no longer writes), so that
    logging costs an append whatever the batch holds.  Past ``cap``
    entries, duplicates counted, it keeps nothing more and :meth:`take`
    says so: the consumer's whole-array road is the cheaper one by then,
    and a bulk load piles up nothing a patch will never use."""

    __slots__ = ("cap", "_slots", "_arrays", "_size")

    def __init__(self, cap: float = math.inf):
        self.cap = cap
        self._slots: List[int] = []
        self._arrays: List[np.ndarray] = []
        self._size = 0

    def add(self, slot: int) -> None:
        if self._size <= self.cap:
            self._size += 1
            self._slots.append(slot)

    def extend(self, slots: np.ndarray) -> None:
        if self._size <= self.cap:
            self._size += slots.size
            if self._size > self.cap:
                self._slots, self._arrays = [], []
            else:
                self._arrays.append(slots)

    def take(self) -> Optional[np.ndarray]:
        """The logged slots, ascending and each once (int64), or None
        where more than ``cap`` were logged; the log starts anew."""
        parts = self._arrays
        if self._slots:
            parts.append(np.asarray(self._slots, dtype=np.int64))
        overflowed = self._size > self.cap
        self._slots, self._arrays, self._size = [], [], 0
        if overflowed:
            return None
        return np.unique(np.concatenate(parts)) if parts else _NO_UIDS


class PackedVerdicts(NamedTuple):
    """A device wake's verdicts as the host reads them
    (``DecrementalTracer.verdict_words``): the words of the slots in use
    and unmarked, and the number of marks.  What :meth:`compute_marks`
    returns in place of a dense mark vector where the trace ran as the
    device's wake."""

    garbage_w: np.ndarray
    num_live: int


def _stamp_freed(cells: list, slots: np.ndarray, ordinal: int) -> int:
    """Leave wake ``ordinal`` on every cell of ``slots`` that reports its
    own termination (``ActorCell.note_freed``: a local actor's cell; a
    proxy, a foreign slot's ``None`` or a test's stand-in has no such
    method).  Returns how many of them have yet to terminate."""
    stamped = 0
    for slot in slots.tolist():
        note_freed = getattr(cells[slot], "note_freed", None)
        if note_freed is not None and note_freed(ordinal):
            stamped += 1
    return stamped


def _readback(value, site: str) -> np.ndarray:
    """The sanctioned device->host crossing on collector paths:
    materialize ``value`` on host and account the transfer as a
    ``tpu.host_transfer`` event (site + bytes; the device observatory
    attributes it to the active wake phase).  uigc-lint UL011 flags
    unannotated ``np.asarray``/``.item()``/``device_get`` calls under
    ``engines/`` and ``ops/`` — route readbacks through here."""
    out = np.asarray(value)  # readback: the sanctioned crossing itself
    if events.recorder.enabled:
        events.recorder.commit(
            events.HOST_TRANSFER, site=site, bytes=int(out.nbytes)
        )
    return out


def audit_donation(site: str, *bufs) -> None:
    """After a donating jitted call returns: every donated operand must
    have been consumed (``is_deleted()`` true).  A survivor means XLA
    silently copied instead of aliasing — the wake pays double HBM
    traffic at that site every time — committed as ``tpu.donation_copy``
    (the device observatory's donation-audit plane).  Host arrays
    (numpy) handed to a donating call are the same bug by construction:
    nothing can be donated, a device copy is forced."""
    if not events.recorder.enabled:
        return
    for buf in bufs:
        if buf is None:
            continue
        deleted = getattr(buf, "is_deleted", None)
        try:
            consumed = bool(deleted()) if deleted is not None else False
        except Exception:
            continue  # indeterminate (backend quirk): don't cry wolf
        if not consumed:
            events.recorder.commit(
                events.DONATION_COPY,
                site=site,
                bytes=int(getattr(buf, "nbytes", 0) or 0),
            )


class ArrayShadowGraph:
    """Dense-slot shadow graph with kernel-backed tracing."""

    def __init__(
        self,
        context: CrgcContext,
        local_address: Optional[str] = None,
        use_device: bool = False,
        initial_capacity: int = 1024,
        trace_mode: str = "auto",
        pull_density: float = 0.25,
    ):
        from ...ops import pallas_trace as _pt

        self.context = context
        self.local_address = local_address
        #: where the trace runs: False = the host (numpy), True = the
        #: device, as per-wake closure+repair detection relative to the
        #: previous fixpoint (ops/pallas_decremental.py; interpreted
        #: where the platform is no TPU)
        self.use_device = use_device
        #: device-trace propagation strategy (uigc.crgc.trace-mode;
        #: pallas_trace MODE_* docs) + the auto mode's pull threshold
        require(
            trace_mode in _pt.TRACE_MODES, "config.trace_mode",
            "bad uigc.crgc.trace-mode", mode=trace_mode,
            valid=_pt.TRACE_MODES,
        )
        self.trace_mode = trace_mode
        self.pull_density = pull_density
        #: the collector's active wake (telemetry/profile.py), set by
        #: the collector for the length of a wake while a profiler is
        #: attached, else None.  The backend's one road to the profiler:
        #: ``events.wake_phase`` brackets its steps on it (layout,
        #: upload, device, readback, sweep) and ``note`` hands it the
        #: wake's counters.  The wake runs one program with or without
        #: it.
        self.profile_wake = None
        #: capture the marking-parent array on the next trace (the
        #: why-live provenance forest, telemetry/inspect.py).  The
        #: collector sets it per wake only when a liveness inspector
        #: asked for verdict-exact capture, so plain wakes run the
        #: parent-free kernels and pay nothing.
        self.capture_parents = False
        #: (mark, parent) of the last captured trace: ``last_parents[i]``
        #: is the slot whose propagation first marked slot ``i`` at that
        #: verdict, -1 for pseudoroot seeds/unmarked.  Slots on a parent
        #: chain are all marked, so the sweep that follows the capture
        #: never frees a slot the chain names.
        self.last_parents: Optional[np.ndarray] = None
        self.last_parents_mark: Optional[np.ndarray] = None
        #: probe donated buffers after donating jitted calls and commit
        #: ``tpu.donation_copy`` when one survived (see audit_donation).
        #: Enabled by the device observatory's attach
        #: (uigc_tpu/telemetry/Telemetry); off, the donating sites pay
        #: one bool check.
        self.donation_audit = False
        #: accumulated per-edge send matrix: packed (src << 32 | dst)
        #: slot key -> messages sent since enablement.  None (default)
        #: = off; the liveness inspector's attach enables it by
        #: assigning a dict.  Fed by every fold plane; rows naming a
        #: swept slot are purged with the slot.
        self.send_matrix: Optional[Dict[int, int]] = None
        self._dec = None  # lazily-built DecrementalTracer
        #: which implementation the device trace resolved to, recorded
        #: once at the first device wake: "pallas" (Mosaic-compiled, a
        #: TPU) or "pallas-interpret" (the kernel interpreted — CPU test
        #: tier).  None until a device wake ran; a caller that needs the
        #: chip asserts on it rather than trusting the platform default.
        self.trace_impl: Optional[str] = None
        #: device wakes dispatched
        self.device_wakes = 0
        self.total_actors_seen = 0

        cap = max(16, initial_capacity)
        self.capacity = cap
        self.flags = np.zeros(cap, dtype=np.uint8)
        self.recv_count = np.zeros(cap, dtype=np.int64)
        self.supervisor = np.full(cap, -1, dtype=np.int32)
        self.cells: List[Optional["ActorCell"]] = [None] * cap
        self.locations: List[Optional[str]] = [None] * cap

        self.slot_of: Dict["ActorCell", int] = {}
        self.free_slots = IntStack.from_range(0, cap)

        #: packed-plane maps (merge_packed): dense uid -> slot, and the
        #: reverse per-slot uid so freeing a slot invalidates its uid
        #: mapping.  -1 = unmapped.
        self._uid_to_slot = np.full(1024, -1, dtype=np.int64)
        self._slot_uid = np.full(cap, -1, dtype=np.int64)
        #: per-slot flush stamps guarding last-writer-wins writes
        #: against out-of-order ring drains (see _apply_batch)
        self._br_seq = np.full(cap, -1, dtype=np.int64)
        self._sup_seq = np.full(cap, -1, dtype=np.int64)
        self._plane = None
        self._resolve_cell = None
        #: foreign actors (packed.py): the mutator side's dense uid ->
        #: slot, ``_UNSEEN`` before the first row names it and ``_SWEPT``
        #: for good once its slot was freed (a foreign uid resolves
        #: through no registry, so the map itself has to remember that
        #: the actor was proven garbage).  ``_slot_uid`` holds a foreign
        #: slot's uid with ``FOREIGN_BIT`` set; such a slot has no cell.
        self._fuid_to_slot = np.full(1024, _UNSEEN, dtype=np.int64)
        self._has_foreign = False
        #: slots foreign actors hold now: up where they are interned,
        #: down where the sweep frees them (the slots local actors hold
        #: are ``len(slot_of)``); the wake's record takes both
        self.actors_foreign = 0
        #: where the sweep hands the foreign uids to stop and the ones
        #: it freed, once per trace: ``sink(kill_uids, freed_uids)``,
        #: two int64 arrays, on the collector's thread
        #: (``CRGC.set_foreign_sink``)
        self.foreign_sink = None

        ecap = max(16, initial_capacity * 2)
        self.edge_capacity = ecap
        self.edge_src = np.zeros(ecap, dtype=np.int32)
        self.edge_dst = np.zeros(ecap, dtype=np.int32)
        self.edge_weight = np.zeros(ecap, dtype=np.int64)
        #: packed (owner << 32 | target) int64 key -> edge id, the exact
        #: pair.  An edge is allocated iff its weight is nonzero.  A
        #: vectorized hash table, not a dict: the fold's per-batch key
        #: traffic is the collector's hottest map (ops/i64map.py).
        self.edge_of = I64Map()
        self.free_edges = IntStack.from_range(0, ecap)
        #: the allocated edges by either endpoint (ops/edgeindex.py):
        #: told of every id allocated, asked by the sweep for the edges
        #: that hang on the dead, so that a small death costs by the
        #: dead and not by ``edge_capacity``
        self._endpoints = EndpointIndex()
        #: the sweep's membership vector, true at the slots being freed
        #: for the length of :meth:`_free_slots_batch` and nowhere else
        self._dying = np.zeros(cap, dtype=bool)

        #: changelog of pair transitions since the Pallas layout last
        #: consumed it: (insert?, src, dst, kind).  ``None`` means either
        #: "no consumer yet" or "too much churn / geometry change" — the
        #: consumer does a full rebuild (which re-enables the log).  Off
        #: by default so a backend that never consumes it (the host
        #: array) pays one None check per mutation instead of
        #: accumulating up to ``_log_cap`` dead rows.  Four int64
        #: columns (ops/slotmap.PairLog): the fold's batches go in as
        #: the arrays they are, and the layout folds them as arrays.
        self._pair_log: Optional[PairLog] = None
        self._log_cap = 1 << 20
        #: slots whose flags/recv changed since last consumed; enabled
        #: (non-None) by backends that keep node features on a device:
        #: the ``decremental`` backend while it holds ``_resident`` (a
        #: log bounded at ``capacity / _PATCH_SHARE``), the mesh backend
        #: for its sharded arrays (unbounded)
        self._node_log: Optional[_NodeLog] = None
        #: ``(flags_dev, recv_dev)``: the device's copies of ``flags``
        #: and ``recv_count`` at ``capacity``, as the last wake left
        #: them; None until a wake uploaded both whole, and again after
        #: a growth or a failed wake (:meth:`_node_operands`)
        self._resident: Optional[tuple] = None

    # ------------------------------------------------------------- #
    # Capacity management (static-shape friendly: powers of two)
    # ------------------------------------------------------------- #

    def _grow_nodes(self, min_free: int = 1) -> None:
        """Grow in one jump to the power-of-two capacity that yields
        ``min_free`` free slots (as :meth:`_grow_edges` does)."""
        old = self.capacity
        new = old * 2
        while new - old + len(self.free_slots) < min_free:
            new *= 2
        more = new - old
        self.flags = np.concatenate([self.flags, np.zeros(more, dtype=np.uint8)])
        self.recv_count = np.concatenate(
            [self.recv_count, np.zeros(more, dtype=np.int64)]
        )
        self.supervisor = np.concatenate(
            [self.supervisor, np.full(more, -1, dtype=np.int32)]
        )
        self.cells.extend([None] * more)
        self.locations.extend([None] * more)
        self.free_slots.push_range(old, new)
        self._slot_uid = np.concatenate(
            [self._slot_uid, np.full(more, -1, dtype=np.int64)]
        )
        self._br_seq = np.concatenate(
            [self._br_seq, np.full(more, -1, dtype=np.int64)]
        )
        self._sup_seq = np.concatenate(
            [self._sup_seq, np.full(more, -1, dtype=np.int64)]
        )
        self._dying = np.zeros(new, dtype=bool)
        self.capacity = new
        # Node capacity sets the bit-table/supertile geometry: the whole
        # Pallas layout must be rebuilt, and the device's node arrays
        # are of the old capacity.
        self._pair_log = None
        self._dec = None
        self._drop_resident()

    def _grow_edges(self, min_free: int = 1) -> None:
        """Grow in one jump to whatever power-of-two capacity yields
        ``min_free`` free ids — a large batch must not pay one
        array-copy per doubling."""
        old = self.edge_capacity
        new = old * 2
        while new - old + len(self.free_edges) < min_free:
            new *= 2
        self.edge_src = np.concatenate(
            [self.edge_src, np.zeros(new - old, dtype=np.int32)]
        )
        self.edge_dst = np.concatenate(
            [self.edge_dst, np.zeros(new - old, dtype=np.int32)]
        )
        self.edge_weight = np.concatenate(
            [self.edge_weight, np.zeros(new - old, dtype=np.int64)]
        )
        self.free_edges.push_range(old, new)
        self.edge_capacity = new

    # ------------------------------------------------------------- #
    # Interning
    # ------------------------------------------------------------- #

    def slot_for(self, cell: "ActorCell") -> int:
        """Get-or-create the dense slot for an actor (the analogue of
        makeShadow; reference: ShadowGraph.java:45-62)."""
        slot = self.slot_of.get(cell)
        if slot is not None:
            return slot
        if not self.free_slots:
            self._grow_nodes()
        slot = self.free_slots.pop()
        self.total_actors_seen += 1
        self.slot_of[cell] = slot
        self.cells[slot] = cell
        self.locations[slot] = cell.system.address
        self.flags[slot] = _F.FLAG_IN_USE  # not interned, not local
        self.recv_count[slot] = 0
        self.supervisor[slot] = -1
        self._touch(slot)
        return slot

    def _touch(self, slot: int) -> None:
        if self._node_log is not None:
            self._node_log.add(slot)

    def _touch_batch(self, slots: np.ndarray) -> None:
        if self._node_log is not None:
            self._node_log.extend(slots)

    def _log_pair(self, insert: bool, src: int, dst: int, kind: int) -> None:
        """Record a live-pair transition for the incremental Pallas
        layout; collapse to a full-rebuild sentinel under extreme churn."""
        log = self._pair_log
        if log is None:
            return
        # len(log), spelt out: a Python-level __len__ costs a scalar
        # mutation 110 ns, this 30
        if log.in_columns + len(log.staged) >= self._log_cap:
            self._pair_log = None
            return
        log.append((insert, src, dst, kind))

    def _log_pairs_batch(
        self, insert: bool, srcs: np.ndarray, dsts: np.ndarray, kind: int
    ) -> None:
        """Batched :meth:`_log_pair`; collapses to the rebuild sentinel
        when the batch would overflow the log (a sweep that frees a large
        fraction of the graph crosses the layout's repack threshold
        anyway)."""
        log = self._pair_log
        k = len(srcs)
        if log is None or k == 0:
            return
        if len(log) + k > self._log_cap:
            self._pair_log = None
            return
        log.extend(insert, srcs, dsts, kind)

    def _update_edge(self, owner: int, target: int, delta: int) -> None:
        """Zero-count edges are deleted (reference: ShadowGraph.java:64-73)."""
        key = (owner << 32) | target
        eid = self.edge_of.get(key)
        if eid is None:
            if delta == 0:
                return
            if not self.free_edges:
                self._grow_edges()
            eid = self.free_edges.pop()
            self._endpoints.add(eid)
            self.edge_of[key] = eid
            self.edge_src[eid] = owner
            self.edge_dst[eid] = target
            self.edge_weight[eid] = delta
            if delta > 0:
                self._log_pair(True, owner, target, _PAIR_EDGE)
            return
        w_old = self.edge_weight[eid]
        w = w_old + delta
        if w == 0:
            self._free_edge(eid)
        else:
            self.edge_weight[eid] = w
            # The packer layout depends only on edge *liveness* (weight
            # sign), not magnitude; don't invalidate the layout for
            # plain message-count deltas.
            if (w_old > 0) != (w > 0):
                self._log_pair(w > 0, owner, target, _PAIR_EDGE)

    def _free_edge(self, eid: int) -> None:
        owner = int(self.edge_src[eid])
        target = int(self.edge_dst[eid])
        if self.edge_weight[eid] > 0:
            self._log_pair(False, owner, target, _PAIR_EDGE)
        self.edge_of.pop((owner << 32) | target, None)
        self.edge_weight[eid] = 0
        self.free_edges.push(eid)

    def _set_supervisor(self, child_slot: int, new_sup: int) -> None:
        old = int(self.supervisor[child_slot])
        if old == new_sup:
            return
        if old >= 0:
            self._log_pair(False, child_slot, old, _PAIR_SUP)
        if new_sup >= 0:
            self._log_pair(True, child_slot, new_sup, _PAIR_SUP)
        self.supervisor[child_slot] = new_sup

    # ------------------------------------------------------------- #
    # Folding entries (reference: ShadowGraph.java:75-125)
    # ------------------------------------------------------------- #

    def merge_entry(self, entry: Entry) -> None:
        self_slot = self.slot_for(entry.self_ref.target)
        flags = self.flags
        flags[self_slot] |= _F.FLAG_INTERNED | _F.FLAG_LOCAL
        self.recv_count[self_slot] += entry.recv_count
        if entry.is_busy:
            flags[self_slot] |= _F.FLAG_BUSY
        else:
            flags[self_slot] &= ~_F.FLAG_BUSY
        if entry.is_root:
            flags[self_slot] |= _F.FLAG_ROOT
        else:
            flags[self_slot] &= ~_F.FLAG_ROOT
        self._touch(self_slot)

        field_size = self.context.entry_field_size

        for i in range(field_size):
            owner = entry.created_owners[i]
            if owner is None:
                break
            target_slot = self.slot_for(entry.created_targets[i].target)
            owner_slot = self.slot_for(owner.target)
            self._update_edge(owner_slot, target_slot, 1)

        for i in range(field_size):
            child = entry.spawned_actors[i]
            if child is None:
                break
            child_slot = self.slot_for(child.target)
            self._set_supervisor(child_slot, self_slot)

        sm = self.send_matrix
        for i in range(field_size):
            target = entry.updated_refs[i]
            if target is None:
                break
            target_slot = self.slot_for(target.target)
            info = entry.updated_infos[i]
            send_count = refob_info.count(info)
            if send_count > 0:
                self.recv_count[target_slot] -= send_count
                self._touch(target_slot)
                if sm is not None:
                    key = (self_slot << 32) | target_slot
                    sm[key] = sm.get(key, 0) + send_count
            if not refob_info.is_active(info):
                self._update_edge(self_slot, target_slot, -1)

    def merge_entries(self, entries) -> None:
        """Batched fold of a drained entry queue: one pass of Python to
        flatten the object-world entries into slot arrays, then vectorized
        scatter-applies — instead of per-refob field loops per entry
        (reference semantics: ShadowGraph.java:75-125, applied per wake at
        LocalGC.scala:149-177 cadence).

        Equivalent to ``merge_entry`` in queue order: busy/root are
        last-writer-wins per actor, receive counts are commutative sums,
        and edge deltas are aggregated to their per-pair net effect (the
        layout cares only about liveness transitions of the *final* weight
        against the initial one, and intermediate flip-flops fold to
        net no-ops — the same argument slotmap.fold_log documents)."""
        slot_for = self.slot_for
        slot_of_get = self.slot_of.get
        sm = self.send_matrix

        self_slots: List[int] = []
        busyroot: List[int] = []
        recv_deltas: List[int] = []
        ek: List[int] = []  # packed (owner << 32 | target) edge keys
        esign: List[int] = []
        sp_child: List[int] = []
        sp_parent: List[int] = []

        busy = int(_F.FLAG_BUSY)
        root = int(_F.FLAG_ROOT)
        rows_append = self_slots.append
        br_append = busyroot.append
        rd_append = recv_deltas.append
        ek_append = ek.append
        es_append = esign.append

        for entry in entries:
            sc = entry.self_ref._target
            self_slot = slot_of_get(sc)
            if self_slot is None:
                self_slot = slot_for(sc)
            rows_append(self_slot)
            br_append(
                (busy if entry.is_busy else 0) | (root if entry.is_root else 0)
            )
            rd_append(entry.recv_count)

            for owner, target in zip(
                entry.created_owners, entry.created_targets
            ):
                if owner is None:
                    break
                oc = owner._target
                tc = target._target
                os_ = slot_of_get(oc)
                if os_ is None:
                    os_ = slot_for(oc)
                ts = slot_of_get(tc)
                if ts is None:
                    ts = slot_for(tc)
                ek_append((os_ << 32) | ts)
                es_append(1)

            for child in entry.spawned_actors:
                if child is None:
                    break
                cc = child._target
                cs = slot_of_get(cc)
                if cs is None:
                    cs = slot_for(cc)
                sp_child.append(cs)
                sp_parent.append(self_slot)

            for target, info in zip(entry.updated_refs, entry.updated_infos):
                if target is None:
                    break
                tc = target._target
                target_slot = slot_of_get(tc)
                if target_slot is None:
                    target_slot = slot_for(tc)
                send_count = info >> 1
                if send_count > 0:
                    rows_append(target_slot)
                    br_append(-1)  # recv-only row
                    rd_append(-send_count)
                    if sm is not None:
                        key = (self_slot << 32) | target_slot
                        sm[key] = sm.get(key, 0) + send_count
                if info & 1:  # deactivated (refob_info.is_active == False)
                    ek_append((self_slot << 32) | target_slot)
                    es_append(-1)
        self._apply_batch(
            np.asarray(self_slots, dtype=np.int64),
            np.asarray(busyroot, dtype=np.int64),
            np.asarray(recv_deltas, dtype=np.int64),
            np.asarray(ek, dtype=np.int64),
            np.asarray(esign, dtype=np.int64),
            np.asarray(sp_child, dtype=np.int64),
            np.asarray(sp_parent, dtype=np.int64),
        )

    def _apply_batch(
        self,
        sl: np.ndarray,
        br: np.ndarray,
        rd: np.ndarray,
        ek: np.ndarray,
        esign: np.ndarray,
        sp_child: np.ndarray,
        sp_parent: np.ndarray,
        sl_seq: Optional[np.ndarray] = None,
        sp_seq: Optional[np.ndarray] = None,
    ) -> int:
        """The vectorized scatter-applies shared by the fold planes
        (object entries, packed rows, weighted snapshots).  Returns how
        many pairs' net weight the batch changed.

        ``sl``/``br``/``rd`` run in queue order; rows with ``br == -1``
        are recv-only (no busy/root write).  ``ek``/``esign`` are packed
        ``owner << 32 | target`` edge keys with signs, order-free (only
        net deltas matter).  ``sp_child``/``sp_parent`` run in queue
        order (last writer wins a child's supervisor).

        ``sl_seq``/``sp_seq`` (packed plane only): global flush stamps
        for the last-writer-wins writes.  Per-thread rings drain
        independently, so a LATER batch can carry an EARLIER flush of
        the same actor — the stamps let the graph refuse stale busy/
        root/supervisor writes across batches.  Additive facts (recv
        sums, interning, net edge deltas) commute and need no guard.
        The object plane passes None: its single FIFO queue already
        totally orders flushes."""
        if sl.size:
            np.add.at(self.recv_count, sl, rd)
            selfrows = br >= 0
            ssl = sl[selfrows]
            sbr = br[selfrows]
            # Last entry wins busy/root: unique() on the reversed slot
            # array returns each slot's first reversed occurrence = its
            # last occurrence in queue order.
            u, ridx = np.unique(ssl[::-1], return_index=True)
            last_bits = sbr[::-1][ridx].astype(np.uint8)
            f = self.flags
            interned = np.uint8(int(_F.FLAG_INTERNED) | int(_F.FLAG_LOCAL))
            keep = np.uint8(0xFF & ~(int(_F.FLAG_BUSY) | int(_F.FLAG_ROOT)))
            if sl_seq is not None:
                seqs = sl_seq[selfrows][::-1][ridx]
                fresh = seqs >= self._br_seq[u]
                self._br_seq[u[fresh]] = seqs[fresh]
                # Interning is monotone — applies even for stale rows.
                f[u] |= interned
                uf = u[fresh]
                f[uf] = (f[uf] & keep) | last_bits[fresh]
            else:
                f[u] = ((f[u] | interned) & keep) | last_bits
            self._touch_batch(sl)

        if sp_child.size:
            u, ridx = np.unique(sp_child[::-1], return_index=True)
            newp = sp_parent[::-1][ridx]
            if sp_seq is not None:
                seqs = sp_seq[::-1][ridx]
                fresh = seqs >= self._sup_seq[u]
                self._sup_seq[u[fresh]] = seqs[fresh]
                u, newp = u[fresh], newp[fresh]
            old = self.supervisor[u].astype(np.int64)
            changed = old != newp
            uu, oo, nn = u[changed], old[changed], newp[changed]
            has_old = oo >= 0
            self._log_pairs_batch(False, uu[has_old], oo[has_old], _PAIR_SUP)
            self._log_pairs_batch(True, uu, nn, _PAIR_SUP)
            self.supervisor[uu] = nn

        if not ek.size:
            return 0
        u, inv = np.unique(ek, return_inverse=True)
        delta = np.zeros(u.size, dtype=np.int64)
        np.add.at(delta, inv, esign)
        nz = delta != 0
        self._apply_edge_deltas(u[nz], delta[nz])
        return int(np.count_nonzero(nz))

    # ------------------------------------------------------------- #
    # Weighted-snapshot fold (the MAC cycle detector's plane)
    # ------------------------------------------------------------- #

    def merge_weighted(
        self,
        sl: np.ndarray,
        br: np.ndarray,
        rd: np.ndarray,
        ek: np.ndarray,
        ew: np.ndarray,
    ) -> bool:
        """Fold a batch of weighted snapshot rows
        (engines/mac/detector.py): ``sl``/``br``/``rd`` as
        :meth:`_apply_batch` takes them (a self row writes busy, last
        writer wins; every row adds ``rd`` to its slot's ``recv_count``,
        under MAC the slot's weight balance), ``ek`` packed
        ``owner << 32 | target`` keys whose pairs' weights change by
        ``ew``, of either sign (a snapshot that enters the candidates
        adds its weights, one that leaves takes them out again; deltas
        commute, so the batch's order is free).  No supervisors, no
        flush stamps: the detector's one queue orders its rows.

        Returns whether the batch changed anything a trace reads (a
        flag, a balance, a pair's weight): an actor that unblocked and
        blocked again with the snapshot it had changes nothing."""
        touched = np.unique(sl)
        flags_before = self.flags[touched]
        recv_before = self.recv_count[touched]
        pairs = self._apply_batch(sl, br, rd, ek, ew, _NO_UIDS, _NO_UIDS)
        return bool(
            pairs
            or (self.flags[touched] != flags_before).any()
            or (self.recv_count[touched] != recv_before).any()
        )

    # ------------------------------------------------------------- #
    # Packed-plane fold (packed.py row layout)
    # ------------------------------------------------------------- #

    def attach_packed_plane(self, plane, resolve_cell) -> None:
        """Wire the engine's packed plane in: ``plane.uid_strong`` pins
        cells named by in-flight rows; ``resolve_cell`` (the system's
        weak uid registry) is the fallback for uids whose pin was
        already consumed."""
        self._plane = plane
        self._resolve_cell = resolve_cell

    def _slots_for_uids(self, uids: np.ndarray) -> np.ndarray:
        """Map uids -> slots, interning unseen ones; -1 where the uid
        names an actor that was already swept, and the caller drops the
        fields naming it.  That is sound, not lossy: the collector
        PROVED the actor garbage, and garbage is monotone, so late facts
        about it (receive deltas, deactivations, edges) change nothing
        the sweep has not already settled.

        A uid with ``FOREIGN_BIT`` goes through the foreign map
        (:meth:`_slots_for_foreign`), every other through the local one
        (:meth:`_slots_for_local`); one batch may hold both."""
        top = int(uids.max(initial=0))
        if top < FOREIGN_BIT:  # all local: the one pass the map needs anyway
            return self._slots_for_local(uids, top)
        is_foreign = uids >= FOREIGN_BIT
        slots = np.empty(uids.shape[0], dtype=np.int64)
        fuids = uids[is_foreign] ^ FOREIGN_BIT
        slots[is_foreign] = self._slots_for_foreign(fuids, top ^ FOREIGN_BIT)
        if fuids.size < uids.size:
            local = uids[~is_foreign]
            slots[~is_foreign] = self._slots_for_local(local, int(local.max()))
        return slots

    @staticmethod
    def _dense_map(m: np.ndarray, top: int, fill: int) -> np.ndarray:
        """``m`` (a dense uid -> slot array), grown to hold uid ``top``."""
        if top < m.shape[0]:
            return m
        grown = max(m.shape[0] * 2, top + 1)
        return np.concatenate([m, np.full(grown - m.shape[0], fill, dtype=np.int64)])

    def _slots_for_local(self, uids: np.ndarray, top: int) -> np.ndarray:
        """Local uids (``ActorCell.uid``; ``top`` is the largest) through
        the dense array, interning unseen ones cell by cell (per-item
        Python, bounded by the spawn rate rather than the flush rate).

        "Swept" and "never seen" are both -1 here and the registries
        tell them apart: a uid resolves through the plane's strong pin
        (held from flush until the actor's slot is swept) or the
        system's weak registry (hit for any cell the runtime still
        references, i.e. every live actor), so an unresolvable uid is a
        swept one."""
        m = self._uid_to_slot = self._dense_map(self._uid_to_slot, top, -1)
        slots = m[uids]
        missing = slots < 0
        if missing.any():
            us = self._plane.uid_strong
            resolve = self._resolve_cell
            for uid in np.unique(uids[missing]).tolist():
                cell = us.get(uid)
                if cell is None:
                    cell = resolve(uid)
                    if cell is None:
                        continue  # proven-garbage uid: fields dropped
                slot = self.slot_for(cell)
                m[uid] = slot
                self._slot_uid[slot] = uid
            slots = m[uids]
        return slots

    def _slots_for_foreign(self, fuids: np.ndarray, top: int) -> np.ndarray:
        """Foreign uids (plain, ``FOREIGN_BIT`` off; ``top`` is the
        largest) through their own dense array, interning all unseen
        ones at once: slots popped in
        bulk and every per-slot array written as an array, no cell and
        no Python per uid.  No registry resolves a foreign uid, so the
        map keeps a tombstone (``_SWEPT``) where a slot was freed: a
        late row naming a swept uid is dropped (-1), never re-interned,
        while a uid never seen before is interned."""
        m = self._fuid_to_slot = self._dense_map(self._fuid_to_slot, top, _UNSEEN)
        slots = m[fuids]
        unseen = slots == _UNSEEN
        if unseen.any():
            new = np.unique(fuids[unseen])
            k = int(new.size)
            # the stack pops lowest-first: the lowest uid, the lowest
            # slot; what is free now goes first, then what growing adds
            have = min(k, len(self.free_slots))
            at = self.free_slots.pop_batch(have)[::-1]
            if have < k:
                self._grow_nodes(min_free=k - have)
                at = np.concatenate([at, self.free_slots.pop_batch(k - have)[::-1]])
            self.flags[at] = _F.FLAG_IN_USE  # not interned, not local
            self.recv_count[at] = 0
            self.supervisor[at] = -1
            self._slot_uid[at] = new | FOREIGN_BIT
            m[new] = at
            self.total_actors_seen += k
            self.actors_foreign += k
            self._has_foreign = True
            self._touch_batch(at)
            slots = m[fuids]
        return np.where(slots == _SWEPT, -1, slots)

    def merge_packed(self, rows: np.ndarray) -> None:
        """Fold a drained batch of packed rows: restore global flush
        order from the seq column, map uids to slots, and run the same
        vectorized scatter-applies as the object plane — semantically
        ``merge_entry`` per row, in seq order, with flush stamps
        guarding cross-batch staleness (see _apply_batch) and fields
        naming proven-garbage uids dropped (see _slots_for_uids)."""
        E = self.context.entry_field_size
        seen = self.total_actors_seen
        order = np.argsort(rows[:, 0], kind="stable")
        R = rows[order]

        self_slots = self._slots_for_uids(R[:, 1])
        c0 = 4

        # Created (owner,target) pairs are extracted BEFORE the
        # self-uid keep filter: the facts name only the owner and the
        # target, not the flushing actor, so an unresolvable flusher
        # must not drop edges between two other, still-live actors —
        # an under-counted live edge is exactly the over-collection
        # hazard the soundness invariant forbids (ADVICE r5).
        created = R[:, c0 : c0 + 2 * E]
        ow = created[:, 0::2].ravel()
        tg = created[:, 1::2].ravel()
        vc = ow >= 0
        ow, tg = ow[vc], tg[vc]
        ow_s = self._slots_for_uids(ow) if ow.size else ow
        tg_s = self._slots_for_uids(tg) if tg.size else tg
        cok = (ow_s >= 0) & (tg_s >= 0)
        ow_s, tg_s = ow_s[cok], tg_s[cok]

        if (self_slots < 0).any():
            # Only the flusher's OWN facts (self state, recv delta,
            # spawned children, updated refobs) drop with it.
            keep = self_slots >= 0
            R = R[keep]
            self_slots = self_slots[keep]
        seq = R[:, 0]
        bits = R[:, 2]
        recv = R[:, 3]
        spawned = R[:, c0 + 2 * E : c0 + 3 * E]
        upd = R[:, c0 + 3 * E : c0 + 5 * E]

        sp = spawned.ravel()
        vs = sp >= 0
        sp_s = self._slots_for_uids(sp[vs]) if vs.any() else sp[vs]
        sp_parent = np.repeat(self_slots, E)[vs]
        sp_seq = np.repeat(seq, E)[vs]
        sok = sp_s >= 0
        sp_s, sp_parent, sp_seq = sp_s[sok], sp_parent[sok], sp_seq[sok]

        ut = upd[:, 0::2].ravel()
        ui = upd[:, 1::2].ravel()
        vu = ut >= 0
        ut_s = self._slots_for_uids(ut[vu]) if vu.any() else ut[vu]
        uok = ut_s >= 0
        ut_s = ut_s[uok]
        uiv = ui[vu][uok]
        upd_self = np.repeat(self_slots, E)[vu][uok]

        # busy/root bit pairs -> flag bytes
        lb = np.array(
            [
                0,
                int(_F.FLAG_BUSY),
                int(_F.FLAG_ROOT),
                int(_F.FLAG_BUSY) | int(_F.FLAG_ROOT),
            ],
            dtype=np.int64,
        )
        br = lb[bits & 3]

        send = uiv >> 1
        has_send = send > 0
        deact = (uiv & 1) == 1

        sm = self.send_matrix
        if sm is not None and has_send.any():
            skeys = (upd_self[has_send] << 32) | ut_s[has_send]
            for key, count in zip(skeys.tolist(), send[has_send].tolist()):
                sm[key] = sm.get(key, 0) + count

        sl = np.concatenate([self_slots, ut_s[has_send]])
        brr = np.concatenate([br, np.full(int(has_send.sum()), -1, np.int64)])
        rdd = np.concatenate([recv, -send[has_send]])
        sl_seq = np.concatenate([seq, np.zeros(int(has_send.sum()), np.int64)])

        ek = np.concatenate(
            [(ow_s << 32) | tg_s, (upd_self[deact] << 32) | ut_s[deact]]
        )
        esign = np.concatenate(
            [
                np.ones(ow_s.size, dtype=np.int64),
                np.full(int(deact.sum()), -1, dtype=np.int64),
            ]
        )

        self._apply_batch(
            sl, brr, rdd, ek, esign, sp_s, sp_parent,
            sl_seq=sl_seq, sp_seq=sp_seq,
        )
        if self.profile_wake is not None:
            self.profile_wake.note(
                fold_rows=int(rows.shape[0]),
                uids_interned=self.total_actors_seen - seen,
            )

    def _apply_edge_deltas(self, keys: np.ndarray, deltas: np.ndarray) -> None:
        """Vectorized ``_update_edge`` over unique packed keys with
        nonzero net deltas: bulk id allocation, array scatter, batch dict
        update, and batched liveness-transition logging."""
        eo = self.edge_of
        eids = eo.get_batch(keys)
        existing = eids >= 0

        ex_eids = eids[existing]
        if ex_eids.size:
            w = self.edge_weight
            ex_keys = keys[existing]
            w_old = w[ex_eids]
            w_new = w_old + deltas[existing]
            live_old = w_old > 0
            live_new = w_new > 0
            went_live = ~live_old & live_new
            went_dead = live_old & ~live_new
            if went_live.any():
                self._log_pairs_batch(
                    True,
                    ex_keys[went_live] >> 32,
                    ex_keys[went_live] & 0xFFFFFFFF,
                    _PAIR_EDGE,
                )
            if went_dead.any():
                self._log_pairs_batch(
                    False,
                    ex_keys[went_dead] >> 32,
                    ex_keys[went_dead] & 0xFFFFFFFF,
                    _PAIR_EDGE,
                )
            w[ex_eids] = w_new
            freed = w_new == 0
            if freed.any():
                fr = ex_eids[freed]
                w[fr] = 0
                self.free_edges.push_batch(fr)
                eo.pop_batch(ex_keys[freed])

        new_keys = keys[~existing]
        if new_keys.size:
            d_new = deltas[~existing]
            need = int(new_keys.size)
            if len(self.free_edges) < need:
                self._grow_edges(min_free=need)
            aa = self.free_edges.pop_batch(need)
            self._endpoints.add_batch(aa)
            self.edge_src[aa] = (new_keys >> 32).astype(np.int32)
            self.edge_dst[aa] = (new_keys & 0xFFFFFFFF).astype(np.int32)
            self.edge_weight[aa] = d_new
            eo.put_batch_new(new_keys, aa)
            pos = d_new > 0
            if pos.any():
                self._log_pairs_batch(
                    True,
                    new_keys[pos] >> 32,
                    new_keys[pos] & 0xFFFFFFFF,
                    _PAIR_EDGE,
                )

    def merge_delta(self, delta) -> None:
        """Fold a peer node's compressed batch
        (reference: ShadowGraph.java:127-156)."""
        decoder = delta.decoder()
        slots = [self.slot_for(cell) for cell in decoder]
        for i, delta_shadow in enumerate(delta.shadows):
            slot = slots[i]
            if delta_shadow.interned:
                self.flags[slot] |= _F.FLAG_INTERNED
                if delta_shadow.is_busy:
                    self.flags[slot] |= _F.FLAG_BUSY
                else:
                    self.flags[slot] &= ~_F.FLAG_BUSY
                if delta_shadow.is_root:
                    self.flags[slot] |= _F.FLAG_ROOT
                else:
                    self.flags[slot] &= ~_F.FLAG_ROOT
            self.recv_count[slot] += delta_shadow.recv_count
            self._touch(slot)
            if delta_shadow.supervisor >= 0:
                self._set_supervisor(slot, slots[delta_shadow.supervisor])
            for target_id, count in delta_shadow.outgoing.items():
                self._update_edge(slot, slots[target_id], count)

    def merge_undo_log(self, log) -> None:
        """Halt a dead node's actors and revert its unadmitted effects
        (reference: ShadowGraph.java:158-174).

        The worklist grows while folding: applying admitted created-refs
        can intern previously-unknown target actors, and those must also
        be visited (halted if they lived on the dead node) — the oracle
        gets this by iterating its live from_set list, which visits
        shadows appended mid-fold."""
        cells = list(self.slot_of.keys())
        seen = set(cells)
        i = 0
        while i < len(cells):
            cell = cells[i]
            i += 1
            slot = self.slot_of[cell]
            if self.locations[slot] == log.node_address:
                self.flags[slot] |= _F.FLAG_HALTED
                self._touch(slot)
            field = log.admitted.get(cell)
            if field is not None:
                self.recv_count[slot] += field.message_count
                self._touch(slot)
                for target_cell, count in field.created_refs.items():
                    if target_cell not in seen:
                        seen.add(target_cell)
                        cells.append(target_cell)
                    self._update_edge(slot, self.slot_for(target_cell), count)

    # ------------------------------------------------------------- #
    # Trace + sweep (reference: ShadowGraph.java:205-289)
    # ------------------------------------------------------------- #

    def compute_marks(self):
        """The trace's verdicts: the dense mark vector from the host
        fixpoint, :class:`PackedVerdicts` from the device's wake (the
        sweep takes either, :meth:`_verdict_slots`)."""
        if self.use_device:
            self._note_device_wake()
            with self._device_call() as ev:
                return self._compute_marks_decremental(ev.fields)
        # Host path: slice to the occupancy watermark.  Slots allocate
        # lowest-first (IntStack from_range), so live slots cluster low
        # and the 12-sweep fixpoint need not scan the grown capacity —
        # two O(capacity) scans here replace O(capacity) work in every
        # sweep.  Safe: flags beyond the last in-use slot are zero, and
        # every nonzero-weight edge/supervisor references in-use slots.
        nz = np.flatnonzero(self.flags)
        h = int(nz[-1]) + 1 if nz.size else 0
        enz = np.flatnonzero(self.edge_weight)
        eh = int(enz[-1]) + 1 if enz.size else 0
        mark = np.zeros(self.capacity, dtype=bool)
        if h:
            mark[:h] = trace_ops.trace_marks_np(
                self.flags[:h],
                self.recv_count[:h],
                self.supervisor[:h],
                self.edge_src[:eh],
                self.edge_dst[:eh],
                self.edge_weight[:eh],
            )
        return mark

    def _compute_marks_with_parents(self) -> np.ndarray:
        """Mark fixpoint with why-live parent capture: stores the
        (mark, parent) pair on ``last_parents``/``last_parents_mark``
        and returns the marks.  Marks are bit-identical to
        :meth:`compute_marks` (parity-tested against both kernels), so
        the sweep that consumes them is unchanged.  The device form is
        one extra XLA fixpoint (ops/pallas_trace.py marking_parents_jax
        — the mark MXU kernel cannot attribute sources); the host form
        is the numpy scatter-min twin.  Reached only when
        ``capture_parents`` was set for this wake."""
        if self.use_device:
            from ...ops import pallas_trace as _pt

            with self._device_call() as ev:
                ev.fields["capture_parents"] = True
                mark, parent = _pt.marking_parents_jax(
                    self.flags,
                    self.recv_count,
                    self.supervisor,
                    self.edge_src,
                    self.edge_dst,
                    self.edge_weight,
                )
                mark = _readback(mark, "marks.parents")
                parent = _readback(parent, "parents.capture")
        else:
            mark, parent = trace_ops.trace_marks_np_parents(
                self.flags,
                self.recv_count,
                self.supervisor,
                self.edge_src,
                self.edge_dst,
                self.edge_weight,
            )
        # Both branches materialized host arrays above (the device one
        # through the accounted _readback), so these are plain aliases.
        self.last_parents = parent
        self.last_parents_mark = mark
        return mark

    def _note_device_wake(self) -> None:
        """Count a device wake and, on the first, record which
        implementation the platform resolved it to (``trace_impl``)."""
        self.device_wakes += 1
        if self.trace_impl is None:
            from ...ops import pallas_trace

            if pallas_trace.default_interpret():
                self.trace_impl = "pallas-interpret"
            else:
                self.trace_impl = "pallas"

    @contextmanager
    def _device_call(self):
        """One device call: the ``DEVICE_TRACE`` event (yielded, for its
        fields) and, on the active wake's record, the same bracket as
        ``device_s`` with the trace mode.  The wake is the backend's one
        road to the profiler, so the profiler listens to no event."""
        wake = self.profile_wake
        with events.recorder.timed(events.DEVICE_TRACE) as ev, \
                events.wake_part(wake, "device_s"):
            ev.fields["trace_mode"] = self.trace_mode
            if wake is not None:
                wake.note(trace_mode=self.trace_mode)
            yield ev

    @staticmethod
    def _read_sweep_stats(counters: list) -> List[dict]:
        """The wake records' fields of the fixpoints' sweep counters,
        read back from the handles wakes deferred (``_Wake.defer``):
        sweep counts, how many sweeps ran the pointer jump, and the
        per-sweep frontier decomposition, which is where the
        pull-density threshold is tuned from data
        (tools/sweep_profile.py writes the same fields)."""
        from ...ops import pallas_decremental

        out = []
        for stats in pallas_decremental.read_counters(counters):
            fields = {
                key: stats[key]
                for key in ("n_sweeps", "jump_sweeps", "closure_sweeps",
                            "closure_bailed", "gated_tiles")
            }
            for key in ("dirty_chunks", "tiles_skipped", "pull_on", "jump_on"):
                fields["sweep_" + key] = stats[key]
            out.append(fields)
        return out

    def _compute_marks_decremental(self, event: dict) -> PackedVerdicts:
        """Per-wake detection through the decremental tracer
        (ops/pallas_decremental.py; the steady-state analogue of the
        reference's 50ms incremental collect, LocalGC.scala:144-186):
        the wake repairs the region the churn may have invalidated
        where that region is small, an island that no supervisor chain
        ties to the live set, and otherwise re-derives from the seeds
        once finding the region has cost its share of a derivation.

        The device call in its four steps, each a profiler phase when a
        wake is attached: layout maintenance, upload (the slots of
        ``flags`` and ``recv_count`` written since the wake before, as
        a patch of the device's copies, :meth:`_node_operands`; then
        ``stage_wake``, timed apart), the wake program from dispatch
        (timed apart) until its result is ready, readback (of the
        verdict words: 1/8 of a byte a slot, not a bool vector).
        ``upload_bytes`` is what the upload handed the device for node
        features: the patch's padded index and value arrays, both
        arrays whole where a wake took that road, 0 where no slot was
        written."""
        wake = self.profile_wake
        with events.wake_phase(wake, "layout"):
            dec = self._dec = self._synced_dec()
        try:
            with events.wake_phase(wake, "upload"):
                flags_dev, recv_dev, nbytes = self._node_operands()
                with events.wake_part(wake, "stage_s", "stage"):
                    staged = dec.stage_wake()
                event["upload_bytes"] = nbytes
                if wake is not None:
                    wake.note(upload_bytes=nbytes)
            with events.wake_phase(wake, "device"):
                with events.wake_part(wake, "dispatch_s", "dispatch"):
                    mark_w = dec.wake_device(flags_dev, recv_dev, staged)
                mark_w.block_until_ready()
            if wake is not None:
                # the wake's own counters stay on the device: whoever
                # reads the record pays for their way to the host
                wake.defer(self._read_sweep_stats, dec.last_counters())
            with events.wake_phase(wake, "readback"):
                garbage_w, marked = dec.verdict_words(mark_w)
                return PackedVerdicts(
                    _readback(garbage_w, "marks.decremental"), marked
                )
        except Exception:
            # A poisoned async result surfaces at the wait or at the
            # readback, after the tracer committed state; drop it so the
            # next wake re-derives instead of feeding poisoned arrays,
            # and uploads the node arrays whole: the patch's donated
            # results came off the same stream.
            dec.invalidate()
            self._drop_resident()
            raise

    def _drop_resident(self) -> None:
        """Forget the device's node arrays and the log kept for them:
        the next wake uploads both whole.  A graph that holds none (the
        host backend; the mesh backend, whose log is its own) is left
        as it is."""
        if self._resident is not None:
            self._resident = None
            self._node_log = None

    def _node_operands(self) -> tuple:
        """``(flags_dev, recv_dev, upload_bytes)``: the device's
        ``flags`` and ``recv_count`` brought up to the host's, for
        ``wake_device``.  The copies stay on the device from
        wake to wake (``_resident``) and take, in one donating scatter,
        the current values of the slots logged since (``_node_log``),
        narrowed as ``device_put`` narrows the whole array, so the
        device reads bit for bit what a whole upload gives it.  They
        hang on no fixpoint: ``dec.invalidate()``, a rebuild and a
        repack leave them valid.  Both arrays go up whole, and a new
        log starts, where there are no copies (the first wake, after a
        growth, after a wake that failed) and where the log holds more
        than ``capacity / _PATCH_SHARE`` slots (a bulk load, a mass
        death).  One road, its parameter read from the log's size.
        Where this call or the wake it serves raises, the caller drops
        the copies (:meth:`_drop_resident`): the patch donated them."""
        import jax

        held = self._resident
        dirty = self._node_log.take() if held is not None else None
        if dirty is None:
            resident = jax.device_put(self.flags), jax.device_put(self.recv_count)
            self._node_log = _NodeLog(self.capacity // _PATCH_SHARE)
            nbytes = self.flags.nbytes + self.recv_count.nbytes
            if held is None:  # a capacity's first copies
                resident = self._warm_patches(resident)
        elif dirty.size:
            resident, nbytes = self._patch(held, dirty, _patch_pad(dirty.size))
        else:
            resident, nbytes = held, 0
        self._resident = resident
        return (*resident, nbytes)

    def _patch(self, resident: tuple, dirty: np.ndarray, kp: int) -> tuple:
        """``((flags_dev, recv_dev), bytes handed over)``: the donated
        ``resident`` pair with the host's values written at the
        ``dirty`` slots (ascending), padded to ``kp`` entries with the
        capacity, a slot out of range."""
        flags_dev, recv_dev = resident
        k = dirty.size
        idx = np.full(kp, self.capacity, dtype=np.int32)
        fvals = np.zeros(kp, dtype=np.uint8)
        rvals = np.zeros(kp, dtype=recv_dev.dtype)
        idx[:k] = dirty
        fvals[:k] = self.flags[dirty]
        rvals[:k] = self.recv_count[dirty].astype(rvals.dtype)
        patched = _patch_fn()(flags_dev, recv_dev, idx, fvals, rvals)
        if self.donation_audit:
            audit_donation("decremental.patch", flags_dev, recv_dev)
        return patched, idx.nbytes + fvals.nbytes + rvals.nbytes

    def _warm_patches(self, resident: tuple) -> tuple:
        """Run the patch at every padded length this capacity can meet,
        all padding, so nothing is written: each length is a program of
        its own, and a wake whose churn is the first to reach one would
        compile it amid the traffic."""
        kp = _patch_pad(1)
        while kp <= _patch_pad(self.capacity // _PATCH_SHARE):
            resident, _ = self._patch(resident, _NO_UIDS, kp)
            kp *= 2
        return resident

    def _synced_dec(self):
        """The decremental tracer, synced with the pair log (its one
        construction site): (re)built on a missing tracer, a geometry
        change or a log overflow (``_pair_log is None``); otherwise the
        log is folded in O(changes), and the layout repacked when
        accumulated churn crosses its threshold."""
        from ...ops import pallas_decremental

        dec = self._dec
        log = self._pair_log
        rows = 0 if log is None else len(log)
        rebuilt = dec is None or log is None
        if rebuilt:
            if dec is None or dec.n != self.capacity:
                dec = pallas_decremental.DecrementalTracer(
                    self.capacity,
                    mode=self.trace_mode,
                    pull_density=self.pull_density,
                )
            self._pair_log = PairLog()
        elif rows:
            dec.apply_log(log)
            log.clear()
            rebuilt = dec.layout.needs_repack
        if rebuilt:
            dec.rebuild(
                self.edge_src, self.edge_dst, self.edge_weight, self.supervisor
            )
        if self.profile_wake is not None:
            # the layout phase's work: the rows it folded, and whether
            # it packed the graph anew (a rebuild's log was never read)
            self.profile_wake.note(layout_rows=rows, layout_rebuilt=int(rebuilt))
        self._dec = dec
        return dec

    def trace(self, should_kill: bool) -> int:
        with events.recorder.timed(events.TRACING) as ev:
            if self.capture_parents:
                verdicts = self._compute_marks_with_parents()
            else:
                verdicts = self.compute_marks()
            n_garbage, n_live = self._sweep(should_kill, verdicts)
            ev.fields["num_garbage_actors"] = n_garbage
            ev.fields["num_live_actors"] = n_live
        return n_garbage

    def unmarked_slots(self) -> np.ndarray:
        """The trace without the sweep: the slots in use that the trace
        left unmarked, ascending.  Nothing is stopped and nothing freed:
        a caller whose protocol asks the suspects first (MAC's
        ``CNF``/``ACK`` round) frees them later, by
        :meth:`stop_and_free`."""
        with events.recorder.timed(events.TRACING) as ev:
            garbage_slots, _, n_live = self._verdict_slots(self.compute_marks())
            ev.fields["num_garbage_actors"] = int(garbage_slots.size)
            ev.fields["num_live_actors"] = n_live
        return garbage_slots

    def stop_and_free(self, slots: np.ndarray, message) -> None:
        """The sweep of a caller that reached its verdict by a protocol
        of its own: every cell of ``slots`` is told ``message`` in one
        bulk teardown, then the slots are freed.  The same ``sweep``
        phase, stamps and counters on the active wake's record as
        :meth:`_sweep`'s, so the stop cascade is timed as it is there."""
        wake = self.profile_wake
        with events.wake_phase(wake, "sweep"), events.recorder.timed(events.SWEEP):
            if wake is not None:
                wake.note(freed_local=_stamp_freed(self.cells, slots, wake.ordinal))
            self._kill_slots_bulk(slots, message)
            _, examined = self._free_slots_batch(slots)
            if wake is not None:
                wake.note(
                    kills=int(slots.size),
                    freed=int(slots.size),
                    sweep_edge_slots=examined,
                    actors_local=len(self.slot_of),
                )

    def _verdict_slots(self, verdicts) -> Tuple[np.ndarray, np.ndarray, int]:
        """``(garbage_slots, kill_slots, num_live)`` of a trace's
        verdicts over the graph's ``flags`` and ``supervisor``, which
        the trace read, both ascending: the nonzeros of
        ``trace_ops.garbage_and_kills_np``.  A dense mark vector goes
        through that function; :class:`PackedVerdicts` never become a
        vector: the few nonzero words are expanded to slot ids and the
        kill rule (local, not halted, supervisor marked) is applied to
        those slots alone.  A slot is garbage iff its bit is set (the
        device cleared the bits of slots not in use); a supervisor is
        marked iff it is in use and its garbage bit is clear, since
        marks never leave the in-use set."""
        flags, supervisor = self.flags, self.supervisor
        if not isinstance(verdicts, PackedVerdicts):
            garbage, kill = trace_ops.garbage_and_kills_np(
                flags, supervisor, verdicts
            )
            return (
                np.nonzero(garbage)[0],
                np.nonzero(kill)[0],
                int(np.count_nonzero(verdicts)),
            )
        words = verdicts.garbage_w
        at = np.flatnonzero(words)
        bits = np.unpackbits(
            words[at].view(np.uint8), bitorder="little"
        )  # little-endian words: byte order is bit order
        hit = np.flatnonzero(bits)
        g = (at[hit >> 5] << 5) | (hit & 31)
        f = flags[g]
        sup = supervisor[g].astype(np.int64)
        has_sup = sup >= 0
        sup[~has_sup] = 0
        sup_marked = (
            has_sup
            & ((flags[sup] & _F.FLAG_IN_USE) != 0)
            & ((words[sup >> 5] >> (sup & 31).astype(np.uint32)) & 1 == 0)
        )
        kill = (
            ((f & _F.FLAG_LOCAL) != 0) & ((f & _F.FLAG_HALTED) == 0) & sup_marked
        )
        return g, g[kill], verdicts.num_live

    def _sweep(self, should_kill: bool, verdicts) -> Tuple[int, int]:
        """Act on a trace's verdicts: stop the kill set, free every
        garbage slot, hand the foreign uids among both to the sink, and
        give the active wake's record the counts.  The one sweep of
        every backend; its own profiler phase and timed event, so the
        trace stays exclusive of it.  The sink is called once per
        trace, also with nothing to hand over: to the mutator side that
        is the verdict on what it shipped before this wake.  Returns
        ``(garbage actors, live actors)``."""
        wake = self.profile_wake
        with events.wake_phase(wake, "sweep"), events.recorder.timed(events.SWEEP):
            garbage_slots, kill_slots, n_live = self._verdict_slots(verdicts)
            kill_uids = freed_uids = _NO_UIDS
            examined = 0
            if wake is not None and garbage_slots.size:
                # before the first StopMsg: the stop cascade is timed to
                # the last termination of the cells stamped here
                wake.note(freed_local=_stamp_freed(
                    self.cells, garbage_slots, wake.ordinal
                ))
            if should_kill and kill_slots.size:
                kill_uids = self._kill_slots_bulk(kill_slots)
            if garbage_slots.size:
                freed_uids, examined = self._free_slots_batch(garbage_slots)
            sink = self.foreign_sink
            if sink is not None:
                sink(kill_uids, freed_uids)
            if wake is not None:
                wake.note(
                    kills=int(kill_slots.size) if should_kill else 0,
                    freed=int(garbage_slots.size),
                    kill_uids=int(kill_uids.size),
                    sweep_edge_slots=examined,
                    actors_local=len(self.slot_of),
                    actors_foreign=self.actors_foreign,
                )
        return int(garbage_slots.size), n_live

    def _foreign_among(self, slots: np.ndarray):
        """``(is_foreign, uids)`` of ``slots``: which have no cell but a
        foreign uid, and those uids (plain)."""
        codes = self._slot_uid[slots]
        is_foreign = codes >= FOREIGN_BIT
        return is_foreign, codes[is_foreign] ^ FOREIGN_BIT

    def _kill_slots_bulk(self, kill_slots: np.ndarray, message=StopMsg) -> np.ndarray:
        """Send ``message`` to every kill slot's cell as ONE bulk teardown:
        the finalize cascade is batched per dispatcher (and, for remote
        cells, per peer writer), so a wake that kills K actors costs
        O(batches) dispatcher operations, not O(K).  Slots of foreign
        actors have no cell to tell: their uids are returned, for the
        sink."""
        from ...runtime.cell import tell_bulk

        kill_uids = _NO_UIDS
        if self._has_foreign:
            is_foreign, kill_uids = self._foreign_among(kill_slots)
            kill_slots = kill_slots[~is_foreign]
        cells = self.cells
        tell_bulk((cells[slot], message) for slot in kill_slots.tolist())
        return kill_uids

    def _free_slots_batch(self, garbage_slots: np.ndarray) -> tuple:
        """Free every garbage slot in one vectorized pass (the sweep,
        reference: ShadowGraph.java:273-289).  Returns the foreign uids
        among the freed, for the sink, and the edge slots examined to
        find the dead edges.

        Incident edges (an edge is allocated iff its weight is nonzero)
        are asked of the endpoint index, so a small death costs by the
        dead slots and the references on them, from both ends: a live
        source may hold an edge into garbage (a negative weight, or the
        source halted).  A mass death takes one scan over the flat edge
        arrays instead, cheaper by then than a binary search a slot;
        which, by the dead against the edge capacity.  Either way the
        same ids in the same order, then O(dead edges) map deletions.

        Supervisor pointers *into* a garbage slot need no scan: a live,
        non-halted child marks its supervisor, so the pointing node is
        garbage in the same sweep and its pointer is cleared here too."""
        dying = self._dying
        dying[garbage_slots] = True
        try:
            return self._free_dying(garbage_slots, dying)
        finally:
            dying[garbage_slots] = False

    def _free_dying(self, garbage_slots: np.ndarray, dying: np.ndarray) -> tuple:
        w = self.edge_weight
        if garbage_slots.size * _SCAN_SHARE > self.edge_capacity:
            examined = self.edge_capacity
            eids = np.nonzero(
                (w != 0) & (dying[self.edge_src] | dying[self.edge_dst])
            )[0]
        else:
            eids, examined = self._endpoints.incident(
                garbage_slots, dying, self.edge_src, self.edge_dst, w
            )
        if eids.size:
            srcs = self.edge_src[eids]
            dsts = self.edge_dst[eids]
            live = w[eids] > 0
            self._log_pairs_batch(False, srcs[live], dsts[live], _PAIR_EDGE)
            eo = self.edge_of
            if eids.size * 2 > len(eo):
                # Most edges die: rebuild the key map from the survivors
                # in one pass instead of popping each dead key.
                w[eids] = 0
                alive = np.nonzero(w != 0)[0]
                keys = (self.edge_src[alive].astype(np.int64) << 32) | (
                    self.edge_dst[alive]
                )
                self.edge_of = I64Map.build(keys, alive)
            else:
                eo.pop_batch((srcs.astype(np.int64) << 32) | dsts)
                w[eids] = 0
            self.free_edges.push_batch(eids)

        sup = self.supervisor[garbage_slots]
        has_sup = sup >= 0
        self._log_pairs_batch(
            False, garbage_slots[has_sup], sup[has_sup], _PAIR_SUP
        )
        self.supervisor[garbage_slots] = -1
        self.flags[garbage_slots] = 0
        self.recv_count[garbage_slots] = 0

        # Invalidate packed-plane uid mappings for freed slots.  A
        # proven-garbage actor can never matter again (CRGC garbage is
        # monotone), so any later row naming its uid is droppable, and
        # _slots_for_uids has to know it for swept: a local uid by
        # resolving nowhere once its mapping and its strong pin are gone
        # (dropped here), a foreign uid, which no registry ever
        # resolved, by the tombstone written here.  Slot reuse also
        # resets the flush-stamp guards.
        su = self._slot_uid
        cell_slots = garbage_slots
        freed_foreign = _NO_UIDS
        if self._has_foreign:
            is_foreign, freed_foreign = self._foreign_among(garbage_slots)
            self._fuid_to_slot[freed_foreign] = _SWEPT
            self.actors_foreign -= int(freed_foreign.size)
            cell_slots = garbage_slots[~is_foreign]
        freed_uids = su[cell_slots]
        had_uid = freed_uids >= 0
        if had_uid.any():
            self._uid_to_slot[freed_uids[had_uid]] = -1
            if self._plane is not None:
                pop = self._plane.uid_strong.pop
                for uid in freed_uids[had_uid].tolist():
                    pop(uid, None)
        su[garbage_slots] = -1
        self._br_seq[garbage_slots] = -1
        self._sup_seq[garbage_slots] = -1

        sm = self.send_matrix
        if sm:
            # Traffic rows naming a swept slot die with it: a freed slot
            # may re-intern a different actor, and a proven-garbage
            # actor's history is useless to placement.
            dead_keys = [
                key
                for key in sm
                if dying[key >> 32] or dying[key & 0xFFFFFFFF]
            ]
            for key in dead_keys:
                del sm[key]

        # what a slot holds per cell; a foreign slot holds none of it
        cells = self.cells
        locations = self.locations
        slot_of = self.slot_of
        for slot in cell_slots.tolist():
            cell = cells[slot]
            if cell is not None:
                slot_of.pop(cell, None)
                cells[slot] = None
            locations[slot] = None
        self.free_slots.push_batch(garbage_slots)
        self._touch_batch(garbage_slots)
        return freed_foreign, examined

    # ------------------------------------------------------------- #
    # Waves (reference: ShadowGraph.java:291-299)
    # ------------------------------------------------------------- #

    def start_wave(self) -> int:
        flags = self.flags
        rootmask = (
            ((flags & _F.FLAG_ROOT) != 0)
            & ((flags & _F.FLAG_LOCAL) != 0)
            & ((flags & _F.FLAG_IN_USE) != 0)
        )
        count = 0
        for slot in np.nonzero(rootmask)[0]:
            cell = self.cells[slot]
            if cell is not None:
                count += 1
                cell.tell(WaveMsg)
        return count

    # ------------------------------------------------------------- #
    # Diagnostics
    # ------------------------------------------------------------- #

    @property
    def num_in_use(self) -> int:
        return len(self.slot_of)

    def addresses_in_graph(self) -> Dict[str, int]:
        """Uncollected shadows per node address
        (reference: ShadowGraph.java:331-340, structured instead of
        printed)."""
        counts: Dict[str, int] = {}
        for slot in self.slot_of.values():
            loc = self.locations[slot]
            counts[loc] = counts.get(loc, 0) + 1
        return counts

    def investigate_live_set(self) -> Dict[str, object]:
        """Structured dump of the live set, vectorized over the slot
        arrays (reference: ShadowGraph.java:342-394; same fields as the
        oracle's implementation, differentially tested)."""
        from .shadow import _cell_path

        slots = np.fromiter(
            self.slot_of.values(), np.int64, len(self.slot_of)
        )
        f = self.flags[slots]
        local = (f & _F.FLAG_LOCAL) != 0
        root_slots = slots[(f & _F.FLAG_ROOT) != 0]

        # an edge exists iff weight != 0 (negative = more deactivations
        # seen than creations so far), matching the oracle's outgoing map
        eids = np.nonzero(self.edge_weight != 0)[0]
        esrc = self.edge_src[eids]
        edst = self.edge_dst[eids]
        ew = self.edge_weight[eids]
        out_degree = np.bincount(esrc, minlength=self.capacity)
        local_all = (self.flags & _F.FLAG_LOCAL) != 0
        src_local = local_all[esrc]
        dst_local = local_all[edst]
        ltr = np.nonzero(src_local & ~dst_local)[0]
        local_to_remote = sorted(
            (
                _cell_path(self.cells[int(esrc[e])]),
                _cell_path(self.cells[int(edst[e])]),
                int(ew[e]),
            )
            for e in ltr.tolist()
        )
        return {
            "total": int(slots.size),
            "non_interned": int((~((f & _F.FLAG_INTERNED) != 0)).sum()),
            "roots": int(root_slots.size),
            "busy": int(((f & _F.FLAG_BUSY) != 0).sum()),
            "nonzero_recv": int((self.recv_count[slots] != 0).sum()),
            "nonlocal": int((~local).sum()),
            "root_acquaintances": {
                _cell_path(self.cells[int(s)]): int(out_degree[int(s)])
                for s in root_slots.tolist()
            },
            "local_to_remote": local_to_remote,
            "remote_to_local_count": int((~src_local & dst_local).sum()),
        }

    def count_reachable_from(self, address: str) -> int:
        """(reference: ShadowGraph.java:302-330)"""
        seed = np.zeros(self.capacity, dtype=bool)
        for cell, slot in self.slot_of.items():
            if self.locations[slot] == address:
                seed[slot] = True
        halted = (self.flags & _F.FLAG_HALTED) != 0
        live_edge = self.edge_weight > 0
        esrc = self.edge_src[live_edge]
        edst = self.edge_dst[live_edge]
        mark = seed
        while True:
            active = mark & ~halted
            new_mark = mark.copy()
            if esrc.size:
                new_mark[edst[active[esrc]]] = True
            new_mark &= (self.flags & _F.FLAG_IN_USE) != 0
            new_mark |= mark
            if np.array_equal(new_mark, mark):
                return int(np.count_nonzero(mark))
            mark = new_mark
