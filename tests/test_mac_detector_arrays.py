"""The MAC cycle detector over the shadow-graph backend, wake by wake,
against the plain reference of its equations (benchmark/reference_mac.py).

No runtime: a small model of MAC actors (rc, weight map, children,
``has_sent_blk``) writes the BLK/UNB/ACK stream the engine would and takes
the detector's CNF and KillMsg through stand-in cells, so every wake is
driven by hand and the detector's ``G`` is compared with the reference's on
the same table, exactly, on the host backend and on the device's wake
program (interpreted here).
"""

import os
import sys
from collections import deque
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))

import reference_mac  # noqa: E402

from uigc_tpu.config import Config  # noqa: E402
from uigc_tpu.engines.mac import detector as det  # noqa: E402
from uigc_tpu.engines.mac.engine import CNF, RC_INC, _KillMsg  # noqa: E402
from uigc_tpu.telemetry.profile import WakeProfiler  # noqa: E402

BACKENDS = ("array", "decremental")


class Cell:
    """What the detector touches of an ``ActorCell``."""

    def __init__(self, uid, system):
        self.uid, self.system = uid, system
        self.inbox = []
        self.is_active = True

    def tell(self, msg):
        self.inbox.append(msg)


class Actor:
    def __init__(self, cell, rc=RC_INC):
        self.cell = cell
        self.rc = rc
        self.weights = {cell: RC_INC}  # its own entry
        self.children = 0
        self.blocked = False  # has_sent_blk
        self.owned = 0  # weight an owner outside the table holds


class World:
    """MAC's mutator side as far as the detector sees it."""

    def __init__(self, backend, profiler=None, collect=True):
        system = SimpleNamespace(
            address="test", config=Config({"uigc.mac.shadow-graph": backend})
        )
        self.engine = SimpleNamespace(
            system=system, queue=deque(), wake_profiler=profiler, collect_cycles=collect
        )
        self.detector = det.CycleDetector(self.engine)
        #: the detector's clock, by hand: a wake is a 50 ms tick
        self.now = 0.0
        self.detector.clock = lambda: self.now
        self.system = system
        self.actors = {}
        self.next_uid = 0
        #: what the detector was told last, by uid: the reference's table
        self.table = {}
        self.killed = []

    # -- the protocol, as engines/mac/engine.py writes it ------------- #

    def spawn(self, rc=RC_INC):
        cell = Cell(self.next_uid, self.system)
        self.next_uid += 1
        self.actors[cell.uid] = actor = Actor(cell, rc)
        return actor

    def block(self, a):
        if not a.blocked:
            a.blocked = True
            snapshot = list(a.weights.items())
            self.engine.queue.append(det.BLK(a.cell, a.rc, snapshot, a.children))
            self.table[a.cell.uid] = (
                a.rc, a.children, {t.uid: w for t, w in snapshot}
            )

    def unblock(self, a):
        if a.blocked:
            a.blocked = False
            self.engine.queue.append(det.UNB(a.cell))
            del self.table[a.cell.uid]

    def ring(self, k, held=False, block=True):
        """``k`` actors that each hold their successor with weight 1;
        ``held``: an owner outside keeps weight 254 towards member 0."""
        members = [self.spawn(rc=1) for _ in range(k)]
        for i, a in enumerate(members):
            a.weights[members[(i + 1) % k].cell] = 1
        if held:
            members[0].owned = RC_INC - 1
            members[0].rc += members[0].owned
        if block:
            for a in members:
                self.block(a)
        return members

    def deliver_dec(self, a, weight):
        """``a`` processes a DecMsg (engine.on_message) and blocks again."""
        self.unblock(a)
        a.rc -= weight
        self.block(a)

    def deliver_inc(self, a):
        self.unblock(a)
        a.rc += RC_INC
        self.block(a)

    def deliver_ref(self, a, target):
        """``a`` receives an app message that carries a ref to ``target``."""
        self.unblock(a)
        a.weights[target.cell] = a.weights.get(target.cell, 0) + 1
        self.block(a)

    def touch(self, a):
        """An app message with no refs."""
        self.unblock(a)
        self.block(a)

    # -- a wake ------------------------------------------------------- #

    def wake(self, answer=True):
        """One ``scan()``, checked against the reference; then the cells
        take what the detector sent them (``answer``: CNFs are ACKed)."""
        d = self.detector
        self.now += 0.05
        d.scan()
        asked = set(d.last_asked)
        for a in list(self.actors.values()):
            inbox, a.cell.inbox = a.cell.inbox, []
            for msg in inbox:
                if isinstance(msg, _KillMsg):
                    self.killed.append(a.cell.uid)
                    a.cell.is_active = False
                    del self.actors[a.cell.uid]
                    del self.table[a.cell.uid]
                elif isinstance(msg, CNF) and answer and a.blocked:
                    self.engine.queue.append(det.ACK(a.cell, msg.token))
        cells = d.graph.cells
        pending = {
            cells[s].uid for t in d.pending.values() for s in t.slots.tolist()
        }
        assert asked <= pending or not asked
        want = reference_mac.garbage(self.table, pending - asked)
        assert asked == want, (sorted(asked), sorted(want))
        assert d.candidates == len(reference_mac.candidates(self.table, pending))
        return asked

    def settle(self, wakes=4):
        for _ in range(wakes):
            self.wake()

    def alive(self, members):
        return [a for a in members if a.cell.uid in self.actors]


def uids(members):
    return {a.cell.uid for a in members}


# ------------------------------------------------------------------- #
# the cases ISSUE 52 lists, one by one
# ------------------------------------------------------------------- #


@pytest.mark.parametrize("backend", BACKENDS)
def test_released_ring_is_collected_held_ring_is_not(backend):
    w = World(backend)
    held = w.ring(8, held=True)
    loose = w.ring(8)
    assert w.wake() == uids(loose)  # asked, not yet killed
    assert not w.killed
    w.wake()
    assert set(w.killed) == uids(loose)
    assert w.detector.total_cycles_collected == 1
    # the held ring is released: one DecMsg at member 0
    w.deliver_dec(held[0], held[0].owned)
    assert w.wake() == uids(held)
    w.wake()
    assert set(w.killed) == uids(loose) | uids(held)
    assert not w.detector.blocked and not w.detector.pending
    assert w.detector.graph.num_in_use == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_garbage_ring_that_only_a_garbage_ring_points_to(backend):
    """The parent's closed-SCC rule found ``a`` and never ``b``."""
    w = World(backend)
    a = w.ring(4, block=False)
    b = w.ring(4, block=False)
    a[0].weights[b[0].cell] = 7
    b[0].rc += 7
    for x in a + b:
        w.block(x)
    assert w.wake() == uids(a) | uids(b)
    w.wake()
    assert set(w.killed) == uids(a) | uids(b)


@pytest.mark.parametrize("backend", BACKENDS)
def test_actor_with_children_keeps_its_ring(backend):
    w = World(backend)
    r = w.ring(5, block=False)
    r[2].children = 1
    for x in r:
        w.block(x)
    w.settle()
    assert not w.killed
    # the child goes: its parent blocks again, childless
    w.unblock(r[2])
    r[2].children = 0
    w.block(r[2])
    w.settle()
    assert set(w.killed) == uids(r)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("in_flight", ["dec", "inc", "ref"])
def test_weight_in_flight_keeps_a_ring(backend, in_flight):
    w = World(backend)
    r = w.ring(6, block=False)
    # held by nobody, a closed set of one; where it is to receive the
    # ref, an owner outside holds it until then
    other = w.spawn(rc=5 if in_flight == "ref" else 0)
    if in_flight == "dec":
        # a holder released: its weight is on its way in a DecMsg
        r[3].rc += 9
        arrive = lambda: w.deliver_dec(r[3], 9)
    elif in_flight == "inc":
        # r[2] topped its weight towards r[3] up: the IncMsg is not in yet
        r[2].weights[r[3].cell] += RC_INC
        arrive = lambda: w.deliver_inc(r[3])
    else:
        # r[2] made a ref to r[3] for ``other``: it is in a message
        r[3].rc += RC_INC
        r[2].weights[r[3].cell] += RC_INC - 1
        arrive = lambda: (w.deliver_ref(other, r[3]), w.deliver_dec(other, 5))
    for x in r:
        w.block(x)
    w.block(other)
    first = w.wake()
    assert not (first & uids(r))
    assert first == (set() if in_flight == "ref" else uids([other]))
    w.settle()
    assert not (set(w.killed) & uids(r))
    arrive()
    w.settle()
    assert set(w.killed) == uids(r) | uids([other])


@pytest.mark.parametrize("backend", BACKENDS)
def test_unb_between_cnf_and_ack_costs_the_rest_one_wake(backend):
    w = World(backend)
    a = w.ring(4)
    b = w.ring(4)
    assert w.wake(answer=False) == uids(a) | uids(b)
    # a message that raced the probe reaches a[1] before the CNF
    w.unblock(a[1])
    a[1].rc += 3  # and it stays referenced from outside
    w.block(a[1])
    for x in a + b:
        w.engine.queue.append(det.ACK(x.cell, 1))
    # the token is void; b is asked again in this very wake, a is live
    assert w.wake() == uids(b)
    assert not w.killed
    w.wake()
    assert set(w.killed) == uids(b)
    assert len(w.alive(a)) == 4


@pytest.mark.parametrize("backend", BACKENDS)
def test_slots_are_reused_after_a_kill(backend):
    w = World(backend)
    first = w.ring(6)
    w.wake()
    slots = {w.detector.graph.slot_of[a.cell] for a in first}
    w.settle()
    assert set(w.killed) == uids(first)
    second = w.ring(6, held=True)
    w.wake()
    assert {w.detector.graph.slot_of[a.cell] for a in second} == slots
    w.settle()
    assert len(w.alive(second)) == 6


@pytest.mark.parametrize("backend", BACKENDS)
def test_quiet_wake_calls_neither_fold_nor_device(backend):
    w = World(backend)
    w.ring(4, held=True)
    w.ring(4)
    w.settle()
    graph = w.detector.graph
    calls = []
    graph.merge_weighted = lambda *a: calls.append("fold")
    graph.compute_marks = lambda: calls.append("trace")
    graph._free_slots_batch = lambda *a: calls.append("free")
    for _ in range(3):
        w.detector.scan()
    assert calls == []
    # traffic that changes nothing is folded and not traced
    del graph.merge_weighted
    held = [a for a in w.actors.values()]
    w.touch(held[0])
    w.detector.scan()
    assert calls == []


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_live_target_of_collected_garbage_leaves_the_table(backend):
    """A garbage ring points at an actor that is not blocked: the table
    knows it only as a target, and forgets it with the ring."""
    w = World(backend)
    live = w.spawn()  # busy: it never blocks
    r = w.ring(4, block=False)
    r[1].weights[live.cell] = 3
    for x in r:
        w.block(x)
    graph = w.detector.graph
    assert w.wake() == uids(r)
    # the ring left the candidates to answer: nobody holds weight
    # towards the target any more, and its slot is free already
    assert live.cell not in graph.slot_of
    w.wake()
    assert set(w.killed) == uids(r)
    assert graph.num_in_use == 0 and live.cell.uid in w.actors


def test_bad_backend_is_refused():
    from uigc_tpu.utils.validation import InvariantViolation

    with pytest.raises(InvariantViolation):
        World("tarjan")


def test_a_member_stopped_by_other_hands_is_dropped_not_asked():
    w = World("array")
    r = w.ring(3)
    lone = w.spawn(rc=0)
    w.block(lone)
    lone.cell.is_active = False
    del w.actors[lone.cell.uid], w.table[lone.cell.uid]
    w.detector.scan()
    assert set(w.detector.last_asked) == uids(r)
    assert lone.cell not in w.detector.graph.slot_of


def test_a_token_nobody_answers_is_voided(monkeypatch):
    monkeypatch.setattr(det, "TOKEN_PATIENCE_S", 0.15)  # three wakes
    w = World("array")
    r = w.ring(3)
    assert w.wake(answer=False) == uids(r)
    for _ in range(2):
        assert w.wake(answer=False) == set()
    assert len(w.detector.pending) == 1
    assert w.wake() == uids(r)  # voided and asked again
    w.wake()
    assert set(w.killed) == uids(r)


def test_collect_cycles_off_asks_and_kills_nobody():
    w = World("array", collect=False)
    r = w.ring(3)
    assert w.wake() == uids(r)
    w.settle()
    assert not w.killed and len(w.detector.pending) == 1


# ------------------------------------------------------------------- #
# the wake's record
# ------------------------------------------------------------------- #


@pytest.mark.parametrize("backend", BACKENDS)
def test_wake_record_counters_by_hand(backend):
    prof = WakeProfiler("test")
    w = World(backend, profiler=prof)
    w.ring(4, held=True)  # 4 candidates, member 0 a seed
    loose = w.ring(3)     # 3 candidates, no seed
    parent = w.spawn()    # blocked with a child: no candidate
    parent.children = 1
    w.block(parent)
    w.wake()
    w.touch(loose[0])  # unblocks inside the token: void
    w.wake()
    w.wake()
    w.detector.scan()  # a quiet wake leaves no record
    recs = prof.wakes_since(0.0)
    assert [r["wake"] for r in recs] == [0, 1, 2]
    first, second, third = recs
    want = dict(blk_rows=8, unb_rows=0, ack_rows=0, candidates=4, seeds=1,
                cnf_sent=3, tokens_open=1, tokens_void=0, kills=0)
    assert {k: first[k] for k in want} == want
    assert first["entries"] == 8 and first["garbage"] == 3
    # the void wake: 3 ACKs, then the UNB that voids their token and the
    # BLK that follows it; the three are asked again under a new token
    want = dict(blk_rows=1, unb_rows=1, ack_rows=3, candidates=4, seeds=1,
                cnf_sent=3, tokens_open=1, tokens_void=1, kills=0)
    assert {k: second[k] for k in want} == want
    want = dict(blk_rows=0, unb_rows=0, ack_rows=3, candidates=4, seeds=1,
                cnf_sent=0, tokens_open=0, tokens_void=0, kills=3)
    assert {k: third[k] for k in want} == want
    assert third["freed"] == 3 and "sweep_end_s" in third
    for name in ("ingest", "fold", "sweep"):
        assert name in first["phases"]
    assert first["phases"]["ingest"] > 0 and first["phases"]["fold"] > 0
    assert first["phases"]["trace"] > 0 and third["phases"]["trace"] == 0
    assert third["phases"]["sweep"] > 0
    if backend == "decremental":
        assert first["device_s"] > 0 and first["phases"]["device"] > 0
        assert third["device_s"] == 0
        assert w.detector.graph.trace_impl == "pallas-interpret"


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_detector_wakes_record_carries_the_cpu_clocks(backend):
    """The MAC road brackets through the same ``_Wake``: thread CPU by
    phase, the workers' clocks over the wake and inside ``stop_and_free``'s
    ``sweep``, CPython's collections, as under CRGC."""
    import threading
    import time

    from uigc_tpu.telemetry.profile import PHASES

    told, leave = threading.Event(), threading.Event()

    def work():
        told.wait()
        until = time.thread_time() + 0.03
        while time.thread_time() < until:
            pass
        leave.wait()

    worker = threading.Thread(target=work, daemon=True)
    worker.start()
    prof = WakeProfiler("test", threads=lambda: {"workers": [worker.ident]})
    prof.start()
    try:
        w = World(backend, profiler=prof)
        w.ring(3)
        w.wake()
        told.set()  # the worker runs between the asking wake and the killing one
        time.sleep(0.1)
        w.wake()
        asked, killed = prof.wakes_since(0.0)
    finally:
        prof.close()
        leave.set()
    assert killed["kills"] == 3 and killed["phases"]["sweep"] > 0
    for rec in (asked, killed):
        assert set(rec["phases_cpu"]) == set(PHASES)
        assert 0 < rec["cpu_s"] <= rec["wall_s"] + 0.001
        assert 0 <= rec["cpu_s"] - sum(rec["phases_cpu"].values()) < 0.005
        assert rec["process_cpu_s"] > 0
        for field in ("workers_cpu_s", "workers_cpu_sweep_s", "workers_busy_max_s"):
            assert 0 <= rec[field] < 0.01, field
        assert (rec["gc_s"], rec["gc_sweep_s"]) >= (0.0, 0.0) and rec["gc_full"] >= 0
    assert asked["workers_cpu_gap_s"] is None and 0.03 <= killed["workers_cpu_gap_s"] < 0.06
    assert 0 < killed["phases_cpu"]["sweep"] <= killed["phases"]["sweep"] + 0.001
    assert asked["phases_cpu"]["sweep"] == 0.0
    if backend == "decremental":
        assert 0 < asked["stage_cpu_s"] <= asked["stage_s"] + 0.001
        assert 0 < asked["dispatch_cpu_s"] <= asked["dispatch_s"] + 0.001


def test_no_profiler_no_record_and_no_wake_handle():
    w = World("array")
    w.ring(3)
    w.settle()
    assert w.detector.graph.profile_wake is None
    assert w.engine.wake_profiler is None


# ------------------------------------------------------------------- #
# a seeded random stream
# ------------------------------------------------------------------- #


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_stream_matches_reference_every_wake(backend, seed, monkeypatch):
    # a CNF that nobody answers (``answer`` below) is asked again soon
    monkeypatch.setattr(det, "TOKEN_PATIENCE_S", 0.2)  # four wakes
    rng = np.random.default_rng([seed, 52])
    w = World(backend)
    held = []       # rings an owner outside still holds
    released = []   # rings that must end up killed
    inflight = []   # deliveries yet to happen
    for step in range(28 if backend == "decremental" else 60):
        for _ in range(int(rng.integers(1, 4))):
            op = int(rng.integers(0, 8))
            if op == 0:
                held.append(w.ring(int(rng.integers(2, 9)), held=True))
            elif op == 1:
                released.append(w.ring(int(rng.integers(2, 9))))
            elif op == 2 and held:
                # the owner releases: a DecMsg, delivered now or later
                r = held.pop(int(rng.integers(len(held))))
                released.append(r)
                if rng.random() < 0.5:
                    w.deliver_dec(r[0], r[0].owned)
                else:
                    inflight.append(lambda r=r: w.deliver_dec(r[0], r[0].owned))
            elif op == 3 and held:
                # ping traffic at a resident: changes nothing
                r = held[int(rng.integers(len(held)))]
                w.touch(r[int(rng.integers(len(r)))])
            elif op == 4 and len(held) >= 2:
                # a ref from one held ring to another, carried by a message
                i, j = rng.choice(len(held), size=2, replace=False)
                src, dst = held[i][-1], held[j][0]
                w.unblock(src)
                if dst.cell not in src.weights and dst.owned > 1:
                    # src learns of dst from the owner: weight 1 of what
                    # the owner holds, no change to dst's rc
                    src.weights[dst.cell] = 1
                    dst.owned -= 1
                w.block(src)
            elif op == 5 and released:
                # garbage that points to garbage, made before either blocks
                a = w.ring(int(rng.integers(2, 6)), block=False)
                b = w.ring(int(rng.integers(2, 6)), block=False)
                a[0].weights[b[-1].cell] = 5
                b[-1].rc += 5
                for x in a + b:
                    w.block(x)
                released.extend([a, b])
            elif op == 6 and inflight:
                inflight.pop(int(rng.integers(len(inflight))))()
            elif op == 7 and held:
                # an IncMsg in flight inside a held ring
                r = held[int(rng.integers(len(held)))]
                k = int(rng.integers(len(r)))
                holder, target = r[k], r[(k + 1) % len(r)]
                if len(r) > 1 and holder.cell.uid in w.actors:
                    w.unblock(holder)
                    holder.weights[target.cell] += RC_INC
                    w.block(holder)
                    inflight.append(lambda t=target: w.deliver_inc(t))
        w.wake(answer=rng.random() < 0.85)
    for deliver in inflight:
        deliver()
    w.settle(12)
    assert not w.detector.pending
    want = set().union(*(uids(r) for r in released)) if released else set()
    # op 4 may have tied a released ring to a held one's weight: what the
    # reference calls garbage at the end is what has to be gone
    assert reference_mac.garbage(w.table, ()) == set()
    assert set(w.killed) <= want
    assert len(w.killed) == len(set(w.killed))
    for r in held:
        assert len(w.alive(r)) == len(r)


def test_a_confirmed_ring_is_killed_at_the_tick_after_it_was_asked():
    """Through the runtime: with a 400 ms timer a released cycle is found
    at the first tick after its release and killed at the next."""
    import time

    from test_mac import Drop, Root, Share, Stopped
    from uigc_tpu import ActorTestKit, Behaviors

    kit = ActorTestKit({"uigc.engine": "mac", "uigc.mac.cycle-detection": True,
                        "uigc.mac.wakeup-interval": 400})
    try:
        probe = kit.create_test_probe(timeout_s=15.0)
        root = kit.spawn(Behaviors.setup_root(lambda c: Root(c, probe)), "root")
        root.tell(Share(None))
        time.sleep(0.2)
        t0 = time.perf_counter()
        root.tell(Drop())
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
        assert 0.4 <= time.perf_counter() - t0 < 1.3  # the second tick, not the first
    finally:
        kit.shutdown()


@pytest.mark.parametrize("engine", ["mac", "crgc"])
def test_a_dead_ring_is_freed_by_reference_counts(engine):
    """Through the runtime, CPython's cycle collector off: a terminated
    cell lets go of its behaviour, its context and the engine's hook
    (``ActorCell._finalize``), so a collected ring, whose members hold
    each other's cells, leaves CPython's full collection nothing to find
    (a cell that keeps them is a dozen objects in cycles an actor)."""
    import gc
    import threading
    import time

    from test_mac import Share
    from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, NoRefs, PostStop

    stopped = threading.Semaphore(0)
    size = 16

    class Member(AbstractBehavior):
        def on_message(self, msg):
            if isinstance(msg, Share):
                self.next = msg.ref
            return self

        def on_signal(self, signal):
            if signal is PostStop:
                stopped.release()
            return None

    class Owner(AbstractBehavior):
        def on_message(self, msg):
            ctx = self.context
            ring = [ctx.spawn(Behaviors.setup(Member), f"m{i}") for i in range(size)]
            for i, member in enumerate(ring):
                member.tell(Share(ctx.create_ref(ring[(i + 1) % size], member)), ctx)
            ctx.release(*ring)
            return self

    config = {"uigc.engine": engine, "uigc.mac.cycle-detection": True,
              "uigc.mac.wakeup-interval": 10, "uigc.crgc.wakeup-interval": 10}
    kit = ActorTestKit(config)
    gc.collect()
    gc.disable()
    try:
        owner = kit.spawn(Behaviors.setup_root(Owner), "owner")
        # four rings: a dispatcher's worker keeps the last task it ran
        # (the kill of the last ring) until its next one
        for _ in range(4):
            owner.tell(NoRefs())
            for _ in range(size):
                assert stopped.acquire(timeout=15.0)
        time.sleep(0.2)
        assert gc.collect() < 2 * size
    finally:
        gc.enable()
        kit.shutdown()
