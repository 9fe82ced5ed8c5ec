"""MAC cycle detector: closed garbage sets found by the shadow-graph backend.

The reference's detector only echoes CNF probes at apparently-blocked
actors and "doesn't actually detect garbage" (reference: reference.conf:48,
mac/CycleDetector.scala:42-97).  This detector completes the algorithm:

1. Blocked actors send BLK snapshots carrying their reference count, their
   weight table, and their child count (the protocol channel mirrors
   reference: CycleDetector.scala:16-39, extended with rc/children).
2. The detector keeps the blocked table as array state, an
   ``ArrayShadowGraph`` (engines/crgc/arrays.py) that a wake changes by
   what arrived.  With ``B`` the actors whose latest word is a BLK and the
   candidates ``C`` those of ``B`` that are childless and in no pending
   confirmation:

   - a slot an actor the detector knows; ``recv_count[slot]`` is its
     weight balance, ``rc + RC_INC`` less the weights the candidates'
     snapshots hold towards it (its own entry, ``RC_INC`` included, too).
     A balance other than 0 says that an actor outside ``C`` holds weight
     or that weight is in flight (a ``DecMsg``, an ``IncMsg``, a ref in a
     message): the slot is a seed, ``pseudoroots_np``'s ``recv_count !=
     0``;
   - ``FLAG_BUSY`` on a known slot that is not in ``C`` (a seed as well);
   - a pair ``owner -> target`` of the snapshot's weight while the owner
     is in ``C``, so a mark goes from a live candidate to whatever it can
     still message.  An actor's own entry counts in its balance and is no
     pair: a self-loop carries a mark nowhere.

   An actor that leaves ``C`` (a UNB, a pending token) takes its
   snapshot's pairs and weights out again: signed deltas through the
   backend's weighted fold (``merge_weighted``), which commute.  The trace
   is the CRGC trace over those columns, on the host or as the device's
   wake program, and what it leaves unmarked, ``G``, is the greatest
   closed set of candidates: every holder of a member is a member and no
   weight is outside.  No strongly connected component is computed
   anywhere; a garbage ring that only another garbage ring points to is
   in ``G`` with it.
3. ``G`` is probed with one CNF(token) a wake; members still blocked ACK
   (reference protocol, CycleDetector.scala:63-81), and leave ``C`` while
   they wait, so a later wake's ``G`` is closed without them.  Because
   in-process enqueue order is causal here (single node, like the
   reference's causal-delivery requirement), an app message racing the
   probe always lands before the CNF and triggers UNB, which voids the
   token: its other members return to ``C`` in that wake and are asked
   again by that wake's trace, so a UNB costs the rest of ``G`` one wake.
4. A token whose members all ACKed is garbage: the wake that drains its
   last ACK, the tick after the one that asked, sends the members
   KillMsg and frees their slots.

Cycles containing actors with children are left uncollected (killing a
parent cascades to children the detector can't reason about) — sound but
deliberately incomplete, like the reference's supervisor marking
(ShadowGraph.java:242-267).  Killed members send no ``DecMsg`` for what
they hold, so an actor outside ``G`` that a member pointed to keeps a
balance above 0 and is never a candidate for collection again, as before.

A wake that finds the queue empty and no token open touches neither the
table nor the device and leaves no record, and the trace runs only in a
wake whose batch changed a flag, a balance or a pair.  Under a wake
profiler (``Engine.wake_profiler``) a wake leaves the collector's
record: phases ``ingest`` (the drain into columns), ``fold`` (the
batch), ``sweep`` around kill-and-free, ``trace`` (the verdict's slots
and the probe) with the backend's own ``layout``/``upload``/``device``/
``readback`` inside, and the counters ``blk_rows``, ``unb_rows``,
``ack_rows``, ``candidates``, ``seeds``, ``cnf_sent``, ``tokens_open``,
``tokens_void``, ``kills``.
"""

from __future__ import annotations

import itertools
import time
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from ...ops import trace as trace_ops
from ...runtime.behaviors import RawBehavior
from ...utils import events
from ...utils.validation import require
from ..crgc.arrays import ArrayShadowGraph
from ..crgc.state import CrgcContext
from .engine import CNF, RC_INC, KillMsg

if TYPE_CHECKING:  # pragma: no cover
    from .engine import MAC

#: the values of ``uigc.mac.shadow-graph`` (config.py describes each)
SHADOW_GRAPHS = ("array", "decremental")
#: seconds a token may wait for its ACKs before it is voided and its
#: members asked again: a member that died by other hands (a parent's
#: stop) between the probe's liveness check and the CNF answers nothing
TOKEN_PATIENCE_S = 20.0

_BUSY = int(trace_ops.FLAG_BUSY)
_IN_C_MASK = np.uint8(
    int(trace_ops.FLAG_IN_USE) | int(trace_ops.FLAG_INTERNED) | _BUSY
)
_IN_C = np.uint8(int(trace_ops.FLAG_IN_USE) | int(trace_ops.FLAG_INTERNED))


class BLK:
    """Actor has blocked (reference: CycleDetector.scala:18-23, extended
    with rc and child count for closedness checking).  ``slots`` is the
    detector's: the targets' slots while the snapshot is among the
    candidates, None otherwise; ``token`` the pending confirmation the
    sender is a member of, 0 for none."""

    __slots__ = ("sender", "rc", "actor_map", "num_children", "slots", "token")

    def __init__(self, sender, rc, actor_map, num_children):
        self.sender = sender
        self.rc = rc
        self.actor_map = actor_map  # list of (target_cell, weight)
        self.num_children = num_children
        self.slots: Optional[List[int]] = None
        self.token = 0


class UNB:
    """Actor unblocked after BLK (reference: CycleDetector.scala:25-29)."""

    __slots__ = ("sender",)

    def __init__(self, sender):
        self.sender = sender


class ACK:
    """Actor confirms it is still blocked (reference:
    CycleDetector.scala:31-38)."""

    __slots__ = ("sender", "token")

    def __init__(self, sender, token):
        self.sender = sender
        self.token = token


class Audit:
    """Ask the detector, through its mailbox and so on its own thread,
    for :meth:`CycleDetector.audit`; ``reply`` is called with it."""

    __slots__ = ("reply",)

    def __init__(self, reply: Callable[[tuple], None]):
        self.reply = reply


class _Wakeup:
    """The timer's tick."""

    __slots__ = ()


WAKEUP = _Wakeup()


class _Token:
    """One pending confirmation: a wake's ``G`` by slot, the ACKs still
    to come, and when it was asked (the detector's clock)."""

    __slots__ = ("slots", "waiting", "asked")

    def __init__(self, slots: np.ndarray, asked: float):
        self.slots = slots
        self.waiting = int(slots.size)
        self.asked = asked


class _Rows:
    """A wake's batch as the columns ``merge_weighted`` takes."""

    __slots__ = ("sl", "br", "rd", "ek", "ew")

    def __init__(self) -> None:
        self.sl: List[int] = []
        self.br: List[int] = []
        self.rd: List[int] = []
        self.ek: List[int] = []
        self.ew: List[int] = []

    def fold_into(self, graph: ArrayShadowGraph) -> bool:
        if not self.sl:
            return False
        return graph.merge_weighted(*(
            np.asarray(column, dtype=np.int64)
            for column in (self.sl, self.br, self.rd, self.ek, self.ew)
        ))


class CycleDetector(RawBehavior):
    """(reference: mac/CycleDetector.scala:42-97, completed)"""

    def __init__(self, engine: "MAC"):
        self.engine = engine
        self.cell: Any = None
        self.total_entries = 0
        #: confirmed garbage sets killed (a wake's ``G`` is one)
        self.total_cycles_collected = 0
        self._timer_keys: list = []
        config = engine.system.config
        impl = config.get_string("uigc.mac.shadow-graph")
        require(
            impl in SHADOW_GRAPHS, "config.mac_shadow_graph",
            "bad uigc.mac.shadow-graph", impl=impl, valid=SHADOW_GRAPHS,
        )
        #: the blocked table: slots, balances (``recv_count``), weighted
        #: pairs; the backend CRGC's ``array`` and ``decremental`` are
        self.graph = ArrayShadowGraph(
            CrgcContext(0, 0),
            engine.system.address,
            use_device=(impl == "decremental"),
            trace_mode=config.get_string("uigc.crgc.trace-mode"),
            pull_density=config.get_float("uigc.crgc.pull-density"),
        )
        #: slot -> the latest BLK of an actor whose latest word it is
        self.blocked: Dict[int, BLK] = {}
        #: outstanding confirmations by token
        self.pending: Dict[int, _Token] = {}
        self._token_counter = itertools.count(1)
        #: how many of ``blocked`` are candidates now
        self.candidates = 0
        #: the clock a token's patience is read on
        self.clock = time.monotonic
        #: whom the last wake sent a CNF, its ``G``, by uid
        self.last_asked: List[int] = []

    def bind(self, cell: Any) -> None:
        self.cell = cell
        interval_s = self.engine.system.config.get_int("uigc.mac.wakeup-interval") / 1000.0
        key = ("mac-wakeup", id(self))
        self._timer_keys.append(key)
        self.engine.system.timers.schedule_fixed_delay(
            interval_s, lambda: cell.tell(WAKEUP), key=key
        )

    def stop_timers(self) -> None:
        for key in self._timer_keys:
            self.engine.system.timers.cancel(key)
        self._timer_keys.clear()

    def on_message(self, msg: Any) -> Any:
        if isinstance(msg, _Wakeup):
            self.scan()
        elif isinstance(msg, Audit):
            msg.reply(self.audit())
        return None

    # ------------------------------------------------------------- #
    # The candidates as rows
    # ------------------------------------------------------------- #

    def _enter(self, slot: int, blk: BLK, rows: _Rows) -> None:
        """``blk``'s sender joins the candidates: its own term into its
        balance, its snapshot's weights out of its targets' balances and
        into the pairs."""
        slot_of = self.graph.slot_of.get
        slot_for = self.graph.slot_for
        sl, br, rd, ek, ew = rows.sl, rows.br, rows.rd, rows.ek, rows.ew
        sl.append(slot)
        br.append(0)
        rd.append(blk.rc + RC_INC)
        blk.slots = targets = []
        for cell, weight in blk.actor_map:
            target = slot_of(cell)
            if target is None:
                target = slot_for(cell)
            targets.append(target)
            sl.append(target)
            br.append(-1)
            rd.append(-weight)
            if target != slot:
                ek.append((slot << 32) | target)
                ew.append(weight)
        self.candidates += 1

    def _leave(self, slot: int, blk: BLK, rows: _Rows) -> None:
        """:meth:`_enter` taken back, by the slots it left on ``blk``."""
        sl, br, rd, ek, ew = rows.sl, rows.br, rows.rd, rows.ek, rows.ew
        sl.append(slot)
        br.append(_BUSY)
        rd.append(-(blk.rc + RC_INC))
        for target, (_, weight) in zip(blk.slots, blk.actor_map):
            sl.append(target)
            br.append(-1)
            rd.append(weight)
            if target != slot:
                ek.append((slot << 32) | target)
                ew.append(-weight)
        blk.slots = None
        self.candidates -= 1

    def _unblock(self, slot: int, rows: _Rows) -> int:
        """The actor at ``slot`` is blocked no more: its snapshot goes,
        out of the candidates or out of the confirmation it was part
        of, which that invalidates.  Returns the tokens voided."""
        blk = self.blocked.pop(slot, None)
        if blk is None:
            return 0
        if blk.slots is not None:
            self._leave(slot, blk, rows)
        elif blk.token:
            self._void(blk.token, rows)
            return 1
        return 0

    def _void(self, token: int, rows: _Rows) -> None:
        """Token ``token`` is off: its members that are still blocked
        go back among the candidates."""
        blocked = self.blocked
        for slot in self.pending.pop(token).slots.tolist():
            blk = blocked.get(slot)
            if blk is not None and blk.token == token:
                blk.token = 0
                self._enter(slot, blk, rows)

    # ------------------------------------------------------------- #
    # A wake
    # ------------------------------------------------------------- #

    def scan(self) -> None:
        """Drain the protocol queue into one batch, settle the complete
        confirmations, and if the batch changed the table trace it and
        probe what the trace left unmarked (reference:
        CycleDetector.scala:51-89, completed)."""
        queue = self.engine.queue
        if not queue and not self.pending:
            return
        prof = self.engine.wake_profiler
        wake = prof.begin_wake() if prof is not None else None
        graph = self.graph
        graph.profile_wake = wake
        count = asked = 0
        try:
            count, asked = self._scan(queue, wake)
        finally:
            if wake is not None:
                graph.profile_wake = None
                wake.end(entries=count, garbage=asked)

    def _scan(self, queue, wake: Any) -> Tuple[int, int]:
        graph = self.graph
        blocked = self.blocked
        pending = self.pending
        rows = _Rows()
        n_blk = n_unb = n_ack = n_void = 0
        self.last_asked = []

        with events.wake_phase(wake, "ingest"), \
                events.recorder.timed(events.PROCESSING_MESSAGES) as ev:
            slot_of = graph.slot_of.get
            while True:
                try:
                    msg = queue.popleft()
                except IndexError:
                    break
                if isinstance(msg, BLK):
                    n_blk += 1
                    cell = msg.sender
                    slot = slot_of(cell)
                    if slot is None:
                        # new to the table: in use and not interned, a
                        # seed until a snapshot of its own enters
                        slot = graph.slot_for(cell)
                    else:
                        # a BLK follows a UNB (``has_sent_blk``); one
                        # that does not replaces the snapshot it follows
                        n_void += self._unblock(slot, rows)
                    blocked[slot] = msg
                    if msg.num_children == 0:
                        self._enter(slot, msg, rows)
                elif isinstance(msg, UNB):
                    n_unb += 1
                    slot = slot_of(msg.sender)
                    if slot is not None:
                        n_void += self._unblock(slot, rows)
                elif isinstance(msg, ACK):
                    n_ack += 1
                    token = pending.get(msg.token)
                    if token is not None:
                        token.waiting -= 1
            count = n_blk + n_unb + n_ack
            ev.fields["num_messages"] = count
            self.total_entries += count
            # a token nobody answers (a member stopped by other hands)
            # does not keep its other members for good
            stale = self.clock() - TOKEN_PATIENCE_S
            for token in [t for t, entry in pending.items() if entry.asked <= stale]:
                n_void += 1
                self._void(token, rows)

        with events.wake_phase(wake, "fold"):
            changed = rows.fold_into(graph)

        kills = self._settle()
        asked = 0
        touched = rows.sl
        if changed:
            # the collector's ``trace`` phase: the backend's own phases
            # pause it, what stays is the verdict's slots and the probe
            with events.wake_phase(wake, "trace"):
                asked = self._probe(graph.unmarked_slots(), touched)
        self._forget(touched)
        if wake is not None:
            f = graph.flags
            wake.note(
                blk_rows=n_blk, unb_rows=n_unb, ack_rows=n_ack,
                candidates=self.candidates,
                seeds=int(np.count_nonzero(
                    ((f & _IN_C_MASK) == _IN_C) & (graph.recv_count != 0)
                )),
                cnf_sent=asked, tokens_open=len(pending),
                tokens_void=n_void, kills=kills,
            )
        return count, asked

    def _settle(self) -> int:
        """Kill the confirmations every member of which has ACKed (none
        has sent a UNB since, or the token would be gone) and free their
        slots.  Returns how many actors that were."""
        if not self.engine.collect_cycles:
            return 0
        pending = self.pending
        done = [token for token, entry in pending.items() if entry.waiting <= 0]
        if not done:
            return 0
        slots = np.concatenate([pending.pop(token).slots for token in done])
        blocked = self.blocked
        for slot in slots.tolist():
            del blocked[slot]
        self.total_cycles_collected += len(done)
        # their pairs and weights went when they were asked: what is
        # left of a member is its slot
        self.graph.stop_and_free(slots, KillMsg)
        return int(slots.size)

    def _probe(self, garbage_slots: np.ndarray, touched: List[int]) -> int:
        """Send ``garbage_slots``, a trace's unmarked candidates, one
        CNF(token) and take them out of the candidates while they answer.
        A member whose cell is stopping or has stopped (by its parent's
        hand, not the detector's) is dropped from the table instead: it
        would answer nothing.  Returns how many were asked; the slots
        whose balance that changed go onto ``touched``."""
        if not garbage_slots.size:
            return 0
        from ...runtime.cell import tell_bulk

        graph = self.graph
        blocked = self.blocked
        cells = graph.cells
        rows = _Rows()
        token = next(self._token_counter)
        ask, gone = [], []
        for slot in garbage_slots.tolist():
            blk = blocked[slot]
            self._leave(slot, blk, rows)
            if not getattr(cells[slot], "is_active", True):
                del blocked[slot]
                gone.append(slot)
            else:
                blk.token = token
                ask.append(slot)
        # nothing a trace reads afterwards can be new: the members were
        # unmarked, and as seeds without pairs they mark nobody
        rows.fold_into(graph)
        touched.extend(rows.sl)
        if gone:
            graph._free_slots_batch(np.asarray(gone, dtype=np.int64))
        if not ask:
            return 0
        self.pending[token] = _Token(np.asarray(ask, dtype=np.int64), self.clock())
        asked = [cells[slot] for slot in ask]
        self.last_asked = [cell.uid for cell in asked]
        cnf = CNF(token)
        tell_bulk((cell, cnf) for cell in asked)
        return len(ask)

    def _forget(self, touched: List[int]) -> None:
        """Free the slots among ``touched`` that say nothing any more:
        an actor that is not blocked and towards which no candidate
        holds weight (balance 0) is one the table need not know."""
        if not touched:
            return
        graph = self.graph
        slots = np.unique(np.asarray(touched, dtype=np.int64))
        f = graph.flags[slots]
        idle = (
            ((f & trace_ops.FLAG_IN_USE) != 0)
            & ((f & _IN_C_MASK) != _IN_C)
            & (graph.recv_count[slots] == 0)
        )
        blocked = self.blocked
        free = [slot for slot in slots[idle].tolist() if slot not in blocked]
        if free:
            graph._free_slots_batch(np.asarray(free, dtype=np.int64))

    # ------------------------------------------------------------- #
    # Diagnostics
    # ------------------------------------------------------------- #

    def audit(self) -> tuple:
        """``(table, pending, asked, garbage)`` on the detector's thread:
        the blocked table as ``{uid: (rc, num_children, {uid: weight})}``,
        the uids in a pending confirmation, those of them the last wake
        asked (its ``G``, found on this table with the others pending),
        and the uids a trace of the table as it stands leaves unmarked
        (nobody is asked or freed).  What a plain reference of the
        equations is compared with."""
        cells = self.graph.cells
        table = {
            blk.sender.uid: (
                blk.rc,
                blk.num_children,
                {target.uid: weight for target, weight in blk.actor_map},
            )
            for blk in self.blocked.values()
        }
        waiting: Set[int] = {
            cells[slot].uid
            for entry in self.pending.values()
            for slot in entry.slots.tolist()
        }
        asked = set(self.last_asked)
        garbage = {cells[slot].uid for slot in self.graph.unmarked_slots().tolist()}
        return table, waiting, asked, garbage


__all__ = ["ACK", "Audit", "BLK", "CycleDetector", "UNB"]
